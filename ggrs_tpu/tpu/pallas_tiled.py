"""Entity-tiled pallas kernel: VMEM-resident fused SyncTest at ANY world
size.

The whole-batch kernel (pallas_core) holds the entire world + snapshot ring
in VMEM, which caps it at ~262k entities. Past that the XLA scan runs the
step as dozens of unfused elementwise passes over HBM (~2% of peak
bandwidth at 1M entities). This kernel tiles the ENTITY axis instead: a
1-D pallas grid where each grid step streams one entity tile's state +
ring into VMEM and runs the ENTIRE T-tick batch on it — per batch, every
state/ring byte crosses HBM exactly once in and once out, the ideal-fusion
bound.

What makes the time-inside-tile order legal: the model's step must be
per-entity independent (no cross-entity reductions) and its checksum a
per-entity weighted modular sum. Adapters declare `tileable = True`
(ex_game qualifies; arena's per-team centroids do not — it stays on the
whole-batch kernel or the XLA scan). Checksums are emitted as PARTIAL
per-tile sums accumulated across grid steps in an SMEM revisit buffer
(uint32 wraparound sums are order-invariant, so the total is bit-identical
to the unsharded checksum); the first-seen history compare moves to a
loop-free jnp post-pass over the per-save totals (`first_seen_verdict`:
running maxima over the T*d events, no per-event loop), carrying the same
h_tag/h_hi/h_lo/mismatch state as TpuSyncTestSession's carry, so the tiled
core is a drop-in `backend="pallas-tiled"`.

Save-event layout the post-pass decodes (mirroring TpuSyncTestSession._tick
for tick frame c = c0 + t):
  parts[t, j], j < d-1: rollback re-save of frame (c-d)+1+j  (valid iff c > d)
  parts[t, d-1]:        the save of the current frame c      (always valid)
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .pallas_core import (
    KernelCtx,
    choose_tile_rows,
    derive_checksum_weights,
    get_adapter,
    make_gi_owner,
    partial_checksum_planes,
    plane_groups,
    rebuild_from_planes,
)

LANE = 128


def first_seen_verdict(hc, frames, valid, hi, lo):
    """The first-seen history after a stream of save events, loop-free.

    Bit for bit the result of applying TpuSyncTestSession._save_and_check's
    rule to the events one by one, in order (an invalid event changes
    nothing): an event whose slot `frame % hist` already holds its frame's
    tag compares its checksum with the stored one, any other valid event
    stores its tag and checksum. `hc` holds h_tag/h_hi/h_lo [hist] and the
    mismatch/mismatch_frame scalars; frames/valid/hi/lo are [E].

    What an event meets in its slot is what the last earlier event there
    left: the tag of the last valid one, the checksum of the last one that
    stored (or the carry's, if none). An exclusive running max of event
    indices per slot finds both, so the cost is O(E * hist) with no loop.
    """
    hist = hc["h_tag"].shape[0]
    idx = jnp.arange(frames.shape[0], dtype=jnp.int32)
    slot = frames % hist
    in_slot = slot[:, None] == jnp.arange(hist, dtype=jnp.int32)[None, :]

    def last_marked(mask):
        """Per event, the index of the last EARLIER event in its slot with
        `mask` set; per slot, the last such event overall (-1: none)."""
        marks = jnp.where(in_slot & mask[:, None], idx[:, None], -1)
        upto = jax.lax.cummax(marks, axis=0)
        before = jnp.concatenate(
            [jnp.full((1, hist), -1, jnp.int32), upto[:-1]]
        )
        return jnp.max(jnp.where(in_slot, before, -1), axis=1), upto[-1]

    def pick(values, at, fallback):
        return jnp.where(at >= 0, values[jnp.maximum(at, 0)], fallback)

    tagged_before, tagged_last = last_marked(valid)
    seen = pick(frames, tagged_before, hc["h_tag"][slot]) == frames
    fresh = valid & ~seen
    stored_before, stored_last = last_marked(fresh)
    differs = valid & seen & (
        (pick(hi, stored_before, hc["h_hi"][slot]) != hi)
        | (pick(lo, stored_before, hc["h_lo"][slot]) != lo)
    )
    any_differs = jnp.any(differs)
    return {
        "h_tag": pick(frames, tagged_last, hc["h_tag"]),
        "h_hi": pick(hi, stored_last, hc["h_hi"]),
        "h_lo": pick(lo, stored_last, hc["h_lo"]),
        "mismatch": hc["mismatch"] | any_differs,
        "mismatch_frame": jnp.where(
            any_differs & ~hc["mismatch"],
            frames[jnp.argmax(differs)],
            hc["mismatch_frame"],
        ),
    }


class PallasTiledSyncTestCore:
    """Drop-in batch executor for TpuSyncTestSession's carry, tiled over
    entities (unsharded; any world size that fits HBM)."""

    # per-tile VMEM budget for the streamed windows (state+ring in/out).
    # Mosaic DOUBLE-BUFFERS grid-step windows to overlap DMA with compute,
    # so the effective VMEM cost is ~2x this figure plus temporaries — 28MB
    # keeps the total under the 100MB scoped limit (verified on v5e at 1M
    # entities, check_distance 8)
    VMEM_TILE_BUDGET = 28 * 1024 * 1024

    def __init__(self, game, num_players: int, check_distance: int,
                 interpret: bool = False, tile_rows: int = 0,
                 local_entities: int = 0, external_reduce: bool = False):
        """`local_entities`: when nonzero, the kernel operates on that many
        entities (one shard's slice of the world) while checksum weights
        keep using the GLOBAL entity count — the sharded composition
        (ShardedPallasTiledCore) runs one such local kernel per mesh device
        and psums the partial checksums, which then match the unsharded
        total bit-for-bit.

        `external_reduce`: for reduction-phase adapters — the kernel takes
        a COMPLETE per-frame raw-reduction table `red_raw [d+1, R]` as an
        input instead of computing reductions inline (rows 0..d-1: the
        resim frames base..c-1; row d: the frontier frame c). With the
        reductions injected, the time-inside-tile order and entity
        sharding both become legal for reduce models (the injected values
        don't depend on tile/shard data); the caller owns producing them
        (ShardedPallasTiledCore: local partial sums + psum per tick).
        Single-tick batches only — reductions for tick t+1's frontier
        don't exist at tick t's launch."""
        self.n = local_entities or game.num_entities
        assert self.n % LANE == 0, "entity count must be 128-aligned"
        self.game = game
        self.adapter = get_adapter(game)
        tileable = getattr(self.adapter, "tileable", False)
        self.R = getattr(self.adapter, "reduce_len", 0)
        self.external_reduce = external_reduce
        if external_reduce:
            assert self.R > 0, "external_reduce needs a reduction adapter"
        whole_world = not tileable and not external_reduce
        if whole_world:
            # reduction-phase adapters computing reductions INLINE
            # (arena, unsharded): single whole-world tile only — see
            # PallasTickCore for the rationale
            assert self.R > 0, (
                f"{type(self.adapter).__name__} is neither tileable nor "
                "reduction-declaring; use the whole-batch kernel or XLA"
            )
            assert self.n == game.num_entities, (
                "reduction-phase adapters cannot run on a shard's slice "
                "(local sums would replace the global reduction); use "
                "external_reduce for the sharded composition"
            )
        self.num_players = num_players
        self.input_size = game.input_size
        self.d = check_distance
        self.ring_len = check_distance + 2
        self.n_rows = self.n // LANE
        self.interpret = interpret
        n_planes = len(self.adapter.planes)
        per_row = n_planes * (1 + self.ring_len) * LANE * 4 * 2
        if tile_rows <= 0:
            if whole_world:
                tile_rows = self.n_rows
            else:
                tile_rows = choose_tile_rows(
                    self.n_rows, per_row, self.VMEM_TILE_BUDGET
                )
        if whole_world:
            from .pallas_core import WHOLE_WORLD_TILE_BUDGET

            assert tile_rows == self.n_rows, (
                "reduction-phase adapters require a single whole-world tile"
            )
            assert interpret or per_row * self.n_rows <= WHOLE_WORLD_TILE_BUDGET, (
                f"world too large for the single-tile reduction path "
                f"(~{per_row * self.n_rows >> 20}MB); use the whole-batch "
                "kernel or XLA"
            )
        assert self.n_rows % tile_rows == 0, (
            f"tile_rows {tile_rows} must divide {self.n_rows}"
        )
        # Mosaic block constraint: second-to-last dim divisible by 8, or
        # equal to the full array dim
        assert tile_rows >= 8 or tile_rows == self.n_rows, (
            f"tile_rows {tile_rows} violates the 8-sublane block constraint"
        )
        self.tile_rows = tile_rows
        self.n_tiles = self.n_rows // tile_rows
        self._batch = functools.lru_cache(maxsize=4)(self._build)
        self._cs_entries, self._cs_frame_weight = derive_checksum_weights(
            game, self.adapter
        )

    # -- carry packing (same layout as the whole-batch core) -------------

    def pack(self, carry):
        rows = self.n_rows

        def comp(a, c):
            plane = a if c is None else a[..., c]
            return plane.reshape(plane.shape[: plane.ndim - 1] + (rows, LANE))

        s, r = carry["state"], carry["ring"]
        packed = {}
        for name, key, c in self.adapter.planes:
            packed[name] = comp(s[key], c)
            packed["r_" + name] = comp(r[key], c)
        packed["r_frame"] = r["frame"].astype(jnp.int32)
        packed["iring"] = carry["input_ring"].reshape(
            self.d + 2, self.num_players * self.input_size
        ).astype(jnp.int32)
        return packed

    def unpack(self, p, carry, verdict):
        n = self.n
        groups = plane_groups(self.adapter)
        state = rebuild_from_planes(groups, lambda nm: p[nm], (), n)
        state["frame"] = verdict["frame"]
        ring = rebuild_from_planes(
            groups, lambda nm: p["r_" + nm], (self.ring_len,), n
        )
        ring["frame"] = p["r_frame"]
        return {
            "state": state,
            "ring": ring,
            "input_ring": p["iring"].astype(jnp.uint8).reshape(
                self.d + 2, self.num_players, self.input_size
            ),
            "h_tag": verdict["h_tag"],
            "h_hi": verdict["h_hi"],
            "h_lo": verdict["h_lo"],
            "mismatch": verdict["mismatch"],
            "mismatch_frame": verdict["mismatch_frame"],
            "frame": verdict["frame"],
        }

    # -- kernel ----------------------------------------------------------

    def _build(self, t_ticks: int):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        d, ring_len = self.d, self.ring_len
        rows, tile_rows, P, I = self.n_rows, self.tile_rows, self.num_players, self.input_size
        adapter = self.adapter
        plane_names = [name for name, _, _ in adapter.planes]
        n_tiles = self.n_tiles
        R = self.R if self.external_reduce else 0
        if R:
            assert t_ticks == 1, (
                "external-reduce kernels are single-tick (tick t+1's "
                "frontier reduction doesn't exist at launch)"
            )

        vmem_names = plane_names + ["r_" + n_ for n_ in plane_names]

        def kernel(inputs_ref, c0_ref, iring0_ref, rframe0_ref, red_ref,
                   gi_ref, owner_ref, *refs):
            n_io = len(vmem_names)
            ins = dict(zip(vmem_names, refs[:n_io]))
            outs = dict(zip(vmem_names, refs[n_io : 2 * n_io]))
            parts_hi_ref = refs[2 * n_io]
            parts_lo_ref = refs[2 * n_io + 1]
            rframe_ref = refs[2 * n_io + 2]
            iring_out_ref = refs[2 * n_io + 3]
            iring_scratch = refs[2 * n_io + 4]

            first_tile = pl.program_id(0) == 0

            # local copy of the (tiny, tile-invariant) input ring; every
            # tile evolves it identically from the same batch inputs
            for a in range(d + 2):
                for b in range(P * I):
                    iring_scratch[a, b] = iring0_ref[a, b]
            # seed the revisit buffers from the carry on the first tile
            # (out blocks start uninitialized; later tiles read them after
            # tile 0 ran — the grid is sequential)
            for s in range(ring_len):
                rframe_ref[s] = jnp.where(
                    first_tile, rframe0_ref[s], rframe_ref[s]
                )

            ctx = KernelCtx(gi_ref[:], owner_ref[:])
            out = {n_: outs[n_] for n_ in vmem_names}
            # initialize output windows EXPLICITLY from the input refs:
            # relying on input_output_aliases to pre-fill gridded output
            # windows silently fails past ~1MB planes on real TPUs (one
            # plane reads as zeros — same Mosaic behavior the whole-batch
            # kernel documents for SMEM outs); an in-VMEM copy is cheap
            for n_ in vmem_names:
                out[n_][...] = ins[n_][...]

            def read_state():
                return {n_: out[n_][:] for n_ in plane_names}

            def ring_slot(name, slot):
                return out[name][pl.ds(slot, 1)][0]

            def partial_checksum(state):
                # PARTIAL sums over this tile's entities; global weights
                # ride in via the sliced gi plane. The frame term is folded
                # once by the _verdict post-pass (NOT here), so sharded
                # runs can psum the per-shard partials without multiply-
                # counting it — int32 wraparound adds commute, keeping the
                # total bit-identical to the unsharded checksum.
                return partial_checksum_planes(self._cs_entries, ctx.gi, state)

            def save_tile(state, frame, mask, t, j):
                """Masked ring write + partial-checksum emission into the
                cross-tile accumulator at event (t, j)."""
                hi, lo = partial_checksum(state)
                slot = frame % ring_len
                for name in plane_names:
                    old = ring_slot("r_" + name, slot)
                    out["r_" + name][pl.ds(slot, 1)] = jnp.where(
                        mask, state[name], old
                    )[None]
                old_f = rframe_ref[slot]
                rframe_ref[slot] = jnp.where(
                    first_tile & mask, frame, old_f
                )
                acc_hi = parts_hi_ref[t, j]
                acc_lo = parts_lo_ref[t, j]
                base_hi = jnp.where(first_tile, jnp.int32(0), acc_hi)
                base_lo = jnp.where(first_tile, jnp.int32(0), acc_lo)
                parts_hi_ref[t, j] = base_hi + jnp.where(mask, hi, 0)
                parts_lo_ref[t, j] = base_lo + jnp.where(mask, lo, 0)

            def red_for(row):
                """Finalized reduction values from the injected COMPLETE
                raw sums (row i: resim frame base+i; row d: the frontier).
                None for non-reduce / inline-reduce kernels — step then
                takes its default path."""
                if not R:
                    return None
                raw = [red_ref[row, j] for j in range(R)]
                return adapter.reduce_finalize(raw, ctx)

            def tick(t, _):
                c = c0_ref[0] + t
                do_rb = c > d
                base = jnp.maximum(c - d, 0)
                bslot = base % ring_len
                loaded = {
                    n_: ring_slot("r_" + n_, bslot) for n_ in plane_names
                }
                cur = read_state()
                state = {
                    n_: jnp.where(do_rb, loaded[n_], cur[n_])
                    for n_ in plane_names
                }

                for i in range(d):
                    f = base + i
                    if i > 0:
                        save_tile(state, f, do_rb, t, i - 1)
                    islot = f % (d + 2)
                    inps = [
                        [iring_scratch[islot, p * I + j] for j in range(I)]
                        for p in range(P)
                    ]
                    nxt = (
                        adapter.step(state, inps, ctx, red=red_for(i))
                        if R
                        else adapter.step(state, inps, ctx)
                    )
                    state = {
                        n_: jnp.where(do_rb, nxt[n_], state[n_])
                        for n_ in plane_names
                    }

                save_tile(state, c, jnp.bool_(True), t, d - 1)
                cslot = c % (d + 2)
                new_inps = [
                    [inputs_ref[t, p * I + j] for j in range(I)]
                    for p in range(P)
                ]
                for p in range(P):
                    for j in range(I):
                        iring_scratch[cslot, p * I + j] = new_inps[p][j]
                state = (
                    adapter.step(state, new_inps, ctx, red=red_for(d))
                    if R
                    else adapter.step(state, new_inps, ctx)
                )
                for n_ in plane_names:
                    out[n_][:] = state[n_]
                return 0

            jax.lax.fori_loop(0, t_ticks, tick, 0)

            # evolved input ring out (identical on every tile; revisit
            # buffer keeps the last write)
            for a in range(d + 2):
                for b in range(P * I):
                    iring_out_ref[a, b] = iring_scratch[a, b]

        def state_spec():
            return pl.BlockSpec(
                (tile_rows, LANE), lambda g: (g, 0), memory_space=pltpu.VMEM
            )

        def ring_spec():
            return pl.BlockSpec(
                (ring_len, tile_rows, LANE),
                lambda g: (0, g, 0),
                memory_space=pltpu.VMEM,
            )

        def run(packed, inputs_i32, c0, gi, owner, red_raw=None):
            assert not R or red_raw is not None, (
                "external_reduce kernel launched without its red_raw "
                "table — the caller owns producing the complete per-frame "
                "reduction sums (see ShardedPallasTiledCore)"
            )
            if red_raw is None:
                # dummy row so the operand list is shape-stable across
                # reduce and non-reduce kernels (never read when R == 0)
                red_raw = jnp.zeros((1, 1), jnp.int32)
            in_specs = (
                [
                    pl.BlockSpec(memory_space=pltpu.SMEM),  # inputs [T, P*I]
                    pl.BlockSpec(memory_space=pltpu.SMEM),  # c0 [1]
                    pl.BlockSpec(memory_space=pltpu.SMEM),  # iring0
                    pl.BlockSpec(memory_space=pltpu.SMEM),  # rframe0
                    pl.BlockSpec(memory_space=pltpu.SMEM),  # red_raw [d+1, R]
                    state_spec(),  # gi
                    state_spec(),  # owner
                ]
                + [state_spec() for _ in plane_names]
                + [ring_spec() for _ in plane_names]
            )
            out_specs = (
                [state_spec() for _ in plane_names]
                + [ring_spec() for _ in plane_names]
                + [
                    # cross-tile revisit accumulators: every grid step maps
                    # to the SAME block, so partial sums carry across tiles
                    pl.BlockSpec(
                        (t_ticks, d), lambda g: (0, 0), memory_space=pltpu.SMEM
                    ),
                    pl.BlockSpec(
                        (t_ticks, d), lambda g: (0, 0), memory_space=pltpu.SMEM
                    ),
                    pl.BlockSpec(
                        (ring_len,), lambda g: (0,), memory_space=pltpu.SMEM
                    ),
                    pl.BlockSpec(
                        (d + 2, P * I), lambda g: (0, 0), memory_space=pltpu.SMEM
                    ),
                ]
            )
            out_shapes = (
                [
                    jax.ShapeDtypeStruct((rows, LANE), jnp.int32)
                    for _ in plane_names
                ]
                + [
                    jax.ShapeDtypeStruct((ring_len, rows, LANE), jnp.int32)
                    for _ in plane_names
                ]
                + [
                    jax.ShapeDtypeStruct((t_ticks, d), jnp.int32),
                    jax.ShapeDtypeStruct((t_ticks, d), jnp.int32),
                    jax.ShapeDtypeStruct((ring_len,), jnp.int32),
                    jax.ShapeDtypeStruct((d + 2, P * I), jnp.int32),
                ]
            )
            n_p = len(plane_names)
            # alias state+ring ins (after the 7 leading operands) onto outs
            aliases = {7 + i: i for i in range(2 * n_p)}
            results = pl.pallas_call(
                kernel,
                grid=(n_tiles,),
                in_specs=in_specs,
                out_specs=out_specs,
                out_shape=out_shapes,
                input_output_aliases=aliases,
                scratch_shapes=[
                    pltpu.SMEM((d + 2, P * I), jnp.int32),
                ],
                compiler_params=(
                    None
                    if self.interpret
                    else pltpu.CompilerParams(
                        vmem_limit_bytes=100 * 1024 * 1024
                    )
                ),
                interpret=self.interpret,
            )(
                inputs_i32,
                c0,
                packed["iring"],
                packed["r_frame"],
                red_raw,
                gi,
                owner,
                *[packed[n_] for n_ in plane_names],
                *[packed["r_" + n_] for n_ in plane_names],
            )
            out = dict(zip(vmem_names, results[: 2 * n_p]))
            out["parts_hi"] = results[2 * n_p]
            out["parts_lo"] = results[2 * n_p + 1]
            out["r_frame_new"] = results[2 * n_p + 2]
            out["iring_new"] = results[2 * n_p + 3]
            return out

        return run

    # -- post-pass: first-seen history over the per-save totals ----------

    def _verdict(self, carry, parts_hi, parts_lo, c0, t_ticks):
        """Decode the kernel's T*d save events (module docstring layout)
        and fold them into the session's h_tag/h_hi/h_lo/mismatch exactly
        like TpuSyncTestSession._save_and_check (`first_seen_verdict`)."""
        d = self.d
        t_idx = jnp.arange(t_ticks, dtype=jnp.int32)[:, None]
        j_idx = jnp.arange(d, dtype=jnp.int32)[None, :]
        c = c0 + t_idx
        frames = jnp.where(
            j_idx < d - 1, (c - d) + 1 + j_idx, c
        )  # event frame
        valid = (j_idx == d - 1) | (c > d)
        # fold the frame checksum term here, once per event — the kernel
        # emits pure entity partial sums so sharded runs can psum them
        flat_frames = frames.reshape(-1)
        ev_hi = parts_hi.reshape(-1) + flat_frames * self._cs_frame_weight
        ev_lo = parts_lo.reshape(-1) + flat_frames
        hc = first_seen_verdict(
            carry,
            flat_frames,
            valid.reshape(-1),
            jax.lax.bitcast_convert_type(ev_hi, jnp.uint32),
            jax.lax.bitcast_convert_type(ev_lo, jnp.uint32),
        )
        hc["frame"] = c0 + t_ticks
        return hc

    # -- public ----------------------------------------------------------

    def _planes_at(self, source, slot=None):
        rows = self.n_rows
        out = {}
        for name, key, comp in self.adapter.planes:
            arr = source[key]
            if slot is not None:
                arr = jax.lax.dynamic_index_in_dim(
                    arr, slot, 0, keepdims=False
                )
            plane = arr if comp is None else arr[..., comp]
            out[name] = plane.reshape(rows, LANE)
        return out

    def frontier_partial(self, carry, ctx):
        """Raw reduction partials of the LIVE state (the frontier frame)
        over this core's slice — the one genuinely new row per tick."""
        return jnp.stack(
            self.adapter.reduce_partial(self._planes_at(carry["state"]), ctx)
        )

    def reduce_sources(self, carry, ctx):
        """Per-frame raw-reduction partials over THIS core's (possibly
        local) slice, for one tick at carry["frame"]: rows 0..d-1 from the
        ring slots holding the resim frames base..c-1 (bit-identical to
        the resimulated states by determinism), row d from the live
        state. Early-session rows read zero-init slots — consumed only by
        masked-off resim steps. Sums only: sharded callers psum the
        stacked result before injecting it."""
        d, ring_len = self.d, self.ring_len
        c = carry["frame"]
        base = jnp.maximum(c - d, 0)
        raw = []
        for i in range(d):
            slot = (base + i) % ring_len
            raw.append(
                jnp.stack(
                    self.adapter.reduce_partial(
                        self._planes_at(carry["ring"], slot), ctx
                    )
                )
            )
        raw.append(self.frontier_partial(carry, ctx))
        return jnp.stack(raw)  # [d+1, R]

    def _build_reduce_table(self, S: int):
        """Entity-tiled pallas pre-pass: raw [S, R] reduction tables from S
        stacked plane sources in ONE sweep. Exists because the XLA
        equivalents are pathological at scale on this backend — measured
        at 512k entities / 16 teams: reduce_sources 294 ms and
        frontier_partial 24 ms as unfused masked sums, vs ~1-30 ms for
        the same math streamed through VMEM (the whole 512k 'injection
        boundary' of r4 was THIS, not ring restreaming)."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        plane_names = [name for name, _, _ in self.adapter.planes]
        R, tile_rows, rows = self.R, self.tile_rows, self.n_rows
        adapter = self.adapter

        def kernel(gi_ref, owner_ref, *refs):
            n_p = len(plane_names)
            srcs = dict(zip(plane_names, refs[:n_p]))
            out_ref = refs[n_p]
            first = pl.program_id(0) == 0
            ctx = KernelCtx(gi_ref[:], owner_ref[:])
            for s in range(S):
                planes = {n_: srcs[n_][s] for n_ in plane_names}
                vals = adapter.reduce_partial(planes, ctx)
                for j, v in enumerate(vals):
                    base = jnp.where(first, jnp.int32(0), out_ref[s, j])
                    out_ref[s, j] = base + v

        def state_spec():
            return pl.BlockSpec(
                (tile_rows, LANE), lambda g: (g, 0), memory_space=pltpu.VMEM
            )

        def src_spec():
            return pl.BlockSpec(
                (S, tile_rows, LANE),
                lambda g: (0, g, 0),
                memory_space=pltpu.VMEM,
            )

        def run(sources, gi, owner):
            return pl.pallas_call(
                kernel,
                grid=(self.n_tiles,),
                in_specs=[state_spec(), state_spec()]
                + [src_spec() for _ in plane_names],
                out_specs=[
                    pl.BlockSpec(
                        (S, R), lambda g: (0, 0), memory_space=pltpu.SMEM
                    )
                ],
                out_shape=[jax.ShapeDtypeStruct((S, R), jnp.int32)],
                compiler_params=(
                    None
                    if self.interpret
                    else pltpu.CompilerParams(
                        vmem_limit_bytes=100 * 1024 * 1024
                    )
                ),
                interpret=self.interpret,
            )(gi, owner, *[sources[n_] for n_ in plane_names])[0]

        return run

    def _reduce_runs(self, S: int):
        if not hasattr(self, "_reduce_cache"):
            self._reduce_cache = {}
        if S not in self._reduce_cache:
            self._reduce_cache[S] = self._build_reduce_table(S)
        return self._reduce_cache[S]

    def reduce_sources_kernel(self, carry, gi_offset=0):
        """Kernelized reduce_sources: same [d+1, R] raw table,
        bit-identical (int32 wraparound sums are order-invariant), at
        streaming cost instead of the XLA masked-sum pathology."""
        d, ring_len = self.d, self.ring_len
        c = carry["frame"]
        base = jnp.maximum(c - d, 0)
        sources = {}
        for name, key, comp in self.adapter.planes:
            parts = []
            for i in range(d):
                slot = (base + i) % ring_len
                arr = jax.lax.dynamic_index_in_dim(
                    carry["ring"][key], slot, 0, keepdims=False
                )
                plane = arr if comp is None else arr[..., comp]
                parts.append(plane.reshape(self.n_rows, LANE))
            sp = carry["state"][key]
            plane = sp if comp is None else sp[..., comp]
            parts.append(plane.reshape(self.n_rows, LANE))
            sources[name] = jnp.stack(parts)
        gi, owner = make_gi_owner(self.n_rows, self.num_players, gi_offset)
        return self._reduce_runs(d + 1)(sources, gi, owner)

    def frontier_partial_kernel(self, carry, gi_offset=0):
        """Kernelized frontier_partial: the live state's raw [R] row."""
        sources = {}
        for name, key, comp in self.adapter.planes:
            sp = carry["state"][key]
            plane = sp if comp is None else sp[..., comp]
            sources[name] = plane.reshape(1, self.n_rows, LANE)
        gi, owner = make_gi_owner(self.n_rows, self.num_players, gi_offset)
        return self._reduce_runs(1)(sources, gi, owner)[0]

    def run_kernel(self, carry, inputs, gi_offset=0, red_raw=None):
        """pack -> kernel -> raw outputs (parts NOT yet verdict-folded).
        `gi_offset` shifts the global entity-index plane to this kernel's
        slice of the world; owner derives from it so round-robin ownership
        follows GLOBAL entity ids regardless of sharding. `red_raw`: the
        COMPLETE per-frame reduction table for external_reduce kernels."""
        t = inputs.shape[0]
        run = self._batch(t)
        packed = self.pack(carry)
        inputs_i32 = inputs.reshape(
            t, self.num_players * self.input_size
        ).astype(jnp.int32)
        c0 = carry["frame"].reshape(1).astype(jnp.int32)
        gi, owner = make_gi_owner(self.n_rows, self.num_players, gi_offset)
        out = run(packed, inputs_i32, c0, gi, owner, red_raw)
        out["r_frame"] = out["r_frame_new"]
        out["iring"] = out["iring_new"]
        return out

    def batch(self, carry: Dict[str, Any], inputs) -> Dict[str, Any]:
        t = inputs.shape[0]
        out = self.run_kernel(carry, inputs)
        verdict = self._verdict(
            carry, out["parts_hi"], out["parts_lo"], carry["frame"], t
        )
        return self.unpack(out, carry, verdict)


class ShardedPallasTiledCore:
    """The entity-tiled kernel composed with a device mesh: shard_map over
    the `entity` axis runs one local tiled kernel per device on its slice
    of the world + ring, then psums the per-shard partial checksums (int32
    wraparound sums are order-invariant, so the totals are bit-identical
    to the unsharded kernel's) and runs the first-seen verdict post-pass on
    the replicated totals. Drop-in for TpuSyncTestSession's carry with
    `mesh=` — the multi-chip execution of the SyncTest loop
    (src/sessions/sync_test_session.rs:85-146) at the tiled kernel's
    bandwidth instead of the XLA scan's."""

    def __init__(self, game, num_players: int, check_distance: int,
                 mesh, interpret: bool = False):
        from ..parallel.sharded import entity_shardable

        self.mesh = mesh
        n_shards = mesh.shape.get("entity", 0)
        assert entity_shardable(game.num_entities, mesh, LANE), (
            f"num_entities {game.num_entities} must split into "
            f"{n_shards} 128-aligned shards over the mesh's `entity` axis"
        )
        self.local_n = game.num_entities // n_shards
        adapter = get_adapter(game)
        # reduction-phase adapters (arena) CAN shard — via reduce
        # injection: per tick, every reduction the SyncTest resim needs is
        # computable at launch (resim frames' states sit in the snapshot
        # ring bit-identically; the frontier is the live state), so each
        # tick psums the per-shard raw partials and hands the COMPLETE
        # table to a local external_reduce kernel. Single-tick kernel
        # calls in a scan replace the T-tick batch (the only extra
        # collective is the [d+1, R] psum per tick).
        self.reduce_mode = not getattr(adapter, "tileable", False)
        if self.reduce_mode:
            assert getattr(adapter, "reduce_len", 0) > 0, (
                f"{type(adapter).__name__} is neither tileable nor "
                "reduction-declaring; use the XLA backend"
            )
        self.inner = PallasTiledSyncTestCore(
            game, num_players, check_distance, interpret=interpret,
            local_entities=self.local_n, external_reduce=self.reduce_mode,
        )
        self.game = game
        # reduce-injection cores manage their own jitted programs: the
        # boot-phase table rebuild rides a lax.cond whose both branches
        # execute under SPMD, so steady-state batches compile a SEPARATE
        # program without the cond — selected by a host-tracked frame
        # count an outer jit could never see (sync_test honors
        # self_jitting by not wrapping batch)
        self.self_jitting = self.reduce_mode
        # host-side frame counter DRIVING PROGRAM SELECTION for the
        # self-jitting reduce path: once it passes d, batch() dispatches
        # the steady-state (cond-free) program, whose rolling reduction
        # table assumes every frame in the batch is >= d. It therefore
        # MUST track the carry's frame: reusing this core with a fresh or
        # restored carry without reset() would select the wrong program
        # and emit wrong checksums with no error (the owning session
        # asserts the two counters agree before every dispatch).
        self._frames_seen = 0
        self._programs: Dict[Any, Any] = {}

    def reset(self, start_frame: int = 0) -> None:
        """Re-arm program selection for a fresh or restored carry whose
        frame is `start_frame`: compiled programs survive (they are keyed
        on (batch length, boot?) and carry no frame state), only the
        host-side frame counter rewinds. Call whenever a new carry is
        installed into a reused core — a fresh carry under a stale
        steady-state selection would roll a reduction table whose base the
        boot phase never pinned, silently corrupting checksums."""
        assert start_frame >= 0
        self._frames_seen = start_frame

    @property
    def frames_seen(self) -> int:
        """Frames this core has dispatched (or was reset() to): the owning
        session cross-checks it against its own frame counter so a
        core/carry mismatch trips an assertion instead of selecting the
        wrong program."""
        return self._frames_seen

    def _carry_specs(self, carry):
        from jax.sharding import PartitionSpec as P

        from ..parallel.sharded import ring_specs, state_specs

        return {
            "state": state_specs(carry["state"]),
            "ring": ring_specs(carry["ring"]),
            "input_ring": P(),
            "h_tag": P(),
            "h_hi": P(),
            "h_lo": P(),
            "mismatch": P(),
            "mismatch_frame": P(),
            "frame": P(),
        }

    def batch(self, carry: Dict[str, Any], inputs) -> Dict[str, Any]:
        if self.self_jitting:
            t = int(inputs.shape[0])
            boot = self._frames_seen < self.inner.d
            key = (t, boot)
            if key not in self._programs:
                self._programs[key] = jax.jit(
                    functools.partial(self._batch_program, boot=boot),
                    donate_argnums=(0,),
                )
            self._frames_seen += t
            return self._programs[key](carry, inputs)
        return self._batch_program(carry, inputs, boot=True)

    def _batch_program(self, carry: Dict[str, Any], inputs,
                       boot: bool = True) -> Dict[str, Any]:
        from jax.sharding import PartitionSpec as P

        from .pallas_core import KernelCtx

        inner = self.inner
        t = inputs.shape[0]
        specs = self._carry_specs(carry)

        def body(carry, inputs):
            idx = jax.lax.axis_index("entity")
            offset = idx.astype(jnp.int32) * jnp.int32(self.local_n)
            if not self.reduce_mode:
                out = inner.run_kernel(carry, inputs, offset)
                # the ONLY cross-shard collective in the hot loop:
                # wraparound partial-checksum sums ride ICI; everything
                # else is local
                out["parts_hi"] = jax.lax.psum(out["parts_hi"], "entity")
                out["parts_lo"] = jax.lax.psum(out["parts_lo"], "entity")
                verdict = inner._verdict(
                    carry, out["parts_hi"], out["parts_lo"], carry["frame"],
                    t,
                )
                return inner.unpack(out, carry, verdict)

            # reduce injection: one kernel call per tick, with the
            # per-frame reduction table carried ROLLING through the scan —
            # in steady state (c >= d) this tick's rows 1..d become the
            # next tick's rows 0..d-1 verbatim (same frames, same complete
            # sums), so each tick pays ONE new frontier row + one [R] psum
            # instead of recomputing and psumming all d+1 rows; before the
            # window fills (base pinned at 0, no row shift) the table is
            # rebuilt in full. The boundary tick is exercised by the
            # parity tests (40 frames, d=4). The table math runs through
            # the kernelized pre-passes (reduce_sources_kernel /
            # frontier_partial_kernel) — the XLA masked-sum equivalents
            # cost 294 ms / 24 ms at 512k entities on this backend. The
            # boot-phase rebuild rides a lax.cond whose BOTH branches
            # execute under SPMD (collectives must run uniformly), so the
            # steady-state program (self._booted, host-tracked) drops the
            # cond entirely: once every frame in a batch is >= d, only
            # the frontier row is ever new.
            d = inner.d

            def roll(new_carry, red_raw):
                return jnp.concatenate(
                    [
                        red_raw[1:],
                        jax.lax.psum(
                            inner.frontier_partial_kernel(new_carry, offset),
                            "entity",
                        )[None],
                    ]
                )

            def tick(carry_red, inp_row):
                carry, red_raw = carry_red
                out = inner.run_kernel(
                    carry, inp_row[None], offset, red_raw=red_raw
                )
                out["parts_hi"] = jax.lax.psum(out["parts_hi"], "entity")
                out["parts_lo"] = jax.lax.psum(out["parts_lo"], "entity")
                verdict = inner._verdict(
                    carry, out["parts_hi"], out["parts_lo"], carry["frame"],
                    1,
                )
                new_carry = inner.unpack(out, carry, verdict)
                if boot:
                    next_red = jax.lax.cond(
                        carry["frame"] >= d,  # next base = base+1: rows shift
                        lambda nc: roll(nc, red_raw),
                        lambda nc: jax.lax.psum(
                            inner.reduce_sources_kernel(nc, offset), "entity"
                        ),
                        new_carry,
                    )
                else:
                    next_red = roll(new_carry, red_raw)
                return (new_carry, next_red), None

            red0 = jax.lax.psum(
                inner.reduce_sources_kernel(carry, offset), "entity"
            )
            (carry, _red), _ = jax.lax.scan(tick, (carry, red0), inputs)
            return carry

        shard_fn = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(specs, P()),
            out_specs=specs,
            # pallas outputs defeat replication inference; the replicated
            # outs (iring, verdict carry) are computed identically on every
            # shard from replicated inputs (+psum'd totals)
            check_vma=False,
        )
        return shard_fn(carry, inputs)
