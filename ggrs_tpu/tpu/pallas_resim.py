"""Entity-tiled pallas kernel for ResimCore's generic tick program.

The request path (P2P rollbacks, plain ticks, the lazy multi-tick buffer)
runs ResimCore's control-word-driven tick: optional ring load, then W
masked (save?, advance?) micro-slots. Under XLA that is dozens of unfused
elementwise passes per step — cheap at 4k entities, several ms at 65k+.
This kernel runs T packed tick rows per dispatch tiled over entities:
each grid step streams one tile's state + snapshot ring into VMEM and
executes every row's window loop on it, with the SAME packed control-word
layout ResimCore.pack_tick_row builds (rows ride in SMEM), in-kernel
per-player disconnect-input substitution, and cross-tile partial
checksums. Scalar lanes (state/ring frame fields, the device-verify
history, the returned per-slot checksums with their frame terms) are a
tiny jnp post-pass — a few hundred scalar ops mirroring _tick_impl.

Correctness contract: bit-identical ring/state/checksum outputs to
ResimCore._tick_impl for session-driven control words (the session
invariant start_frame == frame of the first window slot holds by
construction; _verify_update relies on the same invariant). Tileable
adapters only; the XLA scan remains the fallback.

Mesh composition: ShardedPallasTickCore shard_maps one LOCAL kernel per
device over the `entity` axis (exactly the ShardedPallasTiledCore
recipe) and psums the per-shard partial checksums — the flagship
"partitioned world inside a live P2P session" config
(src/sessions/p2p_session.rs:621-673 scaled multi-chip) then runs at the
tiled kernel's bandwidth instead of the XLA scan's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..types import InputStatus
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


class PallasTickCore:
    """Executor for ResimCore's packed tick rows on the entity-tiled
    kernel. One instance per ResimCore; T (rows per dispatch) is the
    compile key (1 for per-tick dispatch, lazy_ticks for the buffer)."""

    VMEM_TILE_BUDGET = 28 * 1024 * 1024

    def __init__(self, core, interpret: bool = False, tile_rows: int = 0,
                 local_entities: int = 0):
        """`local_entities`: when nonzero, the kernel operates on that many
        entities (one shard's slice of the world) while checksum weights
        keep using the GLOBAL entity count — ShardedPallasTickCore runs one
        such local kernel per mesh device and psums the partial checksums,
        which then match the unsharded totals bit-for-bit (the same
        composition ShardedPallasTiledCore uses for the SyncTest batch)."""
        game = core.game
        self.n = local_entities or game.num_entities
        assert self.n % LANE == 0
        self.core = core
        self.game = game
        self.adapter = get_adapter(game)
        tileable = getattr(self.adapter, "tileable", False)
        whole_world = not tileable
        if whole_world:
            # reduction-phase adapters (arena): legal ONLY with whole-world
            # visibility — the kernel runs a single tile so the adapter's
            # inline full-plane reductions are complete. P2P resim states
            # are fresh (corrected inputs), so no per-frame cache applies;
            # a shard's slice would make the sums silently local => wrong.
            assert getattr(self.adapter, "reduce_len", 0) > 0, (
                f"{type(self.adapter).__name__} is neither tileable nor "
                "reduction-declaring; use the XLA backend"
            )
            assert self.n == game.num_entities, (
                "reduction-phase adapters cannot run on a shard's slice "
                "(local sums would replace the global reduction)"
            )
        self.whole_world = whole_world
        self.num_players = core.num_players
        self.input_size = game.input_size
        self.W = core.window
        self.ring_len = core.ring_len
        self.n_rows = self.n // LANE
        self.interpret = interpret
        # the disconnect-substitution row (the reference's dummy input,
        # ex_game.rs:268): games declare it; substitution is per player,
        # exactly the where(status==DISCONNECTED, ...) the model step does
        disc = getattr(game, "disconnect_input", None)
        assert disc is not None and len(disc) == self.input_size, (
            f"{type(game).__name__} must declare disconnect_input "
            "(bytes, input_size long) for the pallas tick path"
        )
        self.disconnect_input = np.frombuffer(
            bytes(disc), dtype=np.uint8
        ).astype(np.int32)
        n_planes = len(self.adapter.planes)
        per_row = n_planes * (1 + self.ring_len + 1) * LANE * 4 * 2
        if tile_rows <= 0:
            if whole_world:
                tile_rows = self.n_rows  # single tile: full-plane sums legal
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
                f"(~{per_row * self.n_rows >> 20}MB of plane windows); use "
                "the XLA backend"
            )
        assert self.n_rows % tile_rows == 0
        assert tile_rows >= 8 or tile_rows == self.n_rows
        self.tile_rows = tile_rows
        self.n_tiles = self.n_rows // tile_rows
        self._run = functools.lru_cache(maxsize=4)(self._build)
        self._cs_entries, self._cs_frame_weight = derive_checksum_weights(
            game, self.adapter
        )

    @classmethod
    def whole_world_fits(cls, game, ring_len) -> bool:
        """Can a reduction-phase (non-tileable) adapter's world run as ONE
        VMEM tile? THE sizing rule the constructor enforces, exposed for
        ResimCore's backend auto-selection."""
        from .pallas_core import WHOLE_WORLD_TILE_BUDGET

        n_planes = len(get_adapter(game).planes)
        per_row = n_planes * (1 + ring_len + 1) * LANE * 4 * 2
        return per_row * (game.num_entities // LANE) <= WHOLE_WORLD_TILE_BUDGET

    # -- packing (ring has ring_len+1 slots; the scratch slot is never
    # -- read or written by a masked save, but it rides along so the
    # -- pytree shape matches ResimCore's exactly) -----------------------

    def pack(self, ring, state):
        rows = self.n_rows
        packed = {}
        for name, key, c in self.adapter.planes:
            s = state[key] if c is None else state[key][..., c]
            r = ring[key] if c is None else ring[key][..., c]
            packed[name] = s.reshape(rows, LANE)
            packed["r_" + name] = r.reshape(r.shape[0], rows, LANE)
        return packed

    def unpack(self, outs, ring, state):
        n = self.n
        groups = plane_groups(self.adapter)
        new_state = rebuild_from_planes(
            groups, lambda nm: outs[nm], (), n
        )
        new_ring = rebuild_from_planes(
            groups, lambda nm: outs["r_" + nm], (self.ring_len + 1,), n
        )
        return new_ring, new_state

    # -- kernel ----------------------------------------------------------

    def _build(self, T: int):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        W, P, I = self.W, self.num_players, self.input_size
        ring_len, rows, tile_rows = self.ring_len, self.n_rows, self.tile_rows
        adapter = self.adapter
        plane_names = [name for name, _, _ in adapter.planes]
        core = self.core
        off_save, off_status, off_input = (
            core._off_save, core._off_status, core._off_input,
        )
        disc = [int(v) for v in self.disconnect_input]
        disconnected = int(InputStatus.DISCONNECTED)

        def kernel(rows_ref, gi_ref, owner_ref, *refs):
            n_p = len(plane_names)
            state_out = dict(zip(plane_names, refs[2 * n_p : 3 * n_p]))
            ring_out = dict(
                zip(plane_names, refs[3 * n_p : 4 * n_p])
            )
            parts_hi_ref = refs[4 * n_p]
            parts_lo_ref = refs[4 * n_p + 1]

            first_tile = pl.program_id(0) == 0
            ctx = KernelCtx(gi_ref[:], owner_ref[:])

            # initialize output windows explicitly from the inputs (the
            # same Mosaic aliasing caveat pallas_tiled documents)
            ins_state = dict(zip(plane_names, refs[:n_p]))
            ins_ring = dict(zip(plane_names, refs[n_p : 2 * n_p]))
            for n_ in plane_names:
                state_out[n_][...] = ins_state[n_][...]
                ring_out[n_][...] = ins_ring[n_][...]

            def ring_slot(name, slot):
                return ring_out[name][pl.ds(slot, 1)][0]

            def tick(t, _):
                do_load = rows_ref[t, 0] != 0
                load_slot = rows_ref[t, 1]
                advance_count = rows_ref[t, 2]
                cur = {n_: state_out[n_][:] for n_ in plane_names}
                loaded = {
                    n_: ring_slot(n_, load_slot) for n_ in plane_names
                }
                state = {
                    n_: jnp.where(do_load, loaded[n_], cur[n_])
                    for n_ in plane_names
                }
                for i in range(W):
                    save_slot = rows_ref[t, off_save + i]
                    do_save = save_slot < ring_len
                    hi, lo = partial_checksum_planes(
                        self._cs_entries, ctx.gi, state
                    )
                    base_hi = jnp.where(
                        first_tile, jnp.int32(0), parts_hi_ref[t, i]
                    )
                    base_lo = jnp.where(
                        first_tile, jnp.int32(0), parts_lo_ref[t, i]
                    )
                    parts_hi_ref[t, i] = base_hi + jnp.where(do_save, hi, 0)
                    parts_lo_ref[t, i] = base_lo + jnp.where(do_save, lo, 0)
                    # masked ring write: scratch-or-beyond slots clamp to 0
                    # with the mask off, leaving slot 0 unchanged
                    wslot = jnp.where(do_save, save_slot, 0)
                    for n_ in plane_names:
                        old = ring_slot(n_, wslot)
                        ring_out[n_][pl.ds(wslot, 1)] = jnp.where(
                            do_save, state[n_], old
                        )[None]
                    # masked step with in-kernel disconnect substitution
                    inps = []
                    for p in range(P):
                        status = rows_ref[t, off_status + i * P + p]
                        row_bytes = []
                        for j in range(I):
                            b = rows_ref[t, off_input + (i * P + p) * I + j]
                            row_bytes.append(
                                jnp.where(
                                    status == disconnected, disc[j], b
                                )
                            )
                        inps.append(row_bytes)
                    nxt = adapter.step(state, inps, ctx)
                    do_adv = i < advance_count
                    state = {
                        n_: jnp.where(do_adv, nxt[n_], state[n_])
                        for n_ in plane_names
                    }
                for n_ in plane_names:
                    state_out[n_][:] = state[n_]
                return 0

            jax.lax.fori_loop(0, T, tick, 0)

        def state_spec():
            return pl.BlockSpec(
                (tile_rows, LANE), lambda g: (g, 0), memory_space=pltpu.VMEM
            )

        def ring_spec():
            return pl.BlockSpec(
                (ring_len + 1, tile_rows, LANE),
                lambda g: (0, g, 0),
                memory_space=pltpu.VMEM,
            )

        def run(packed, rows_i32, gi, owner):
            n_p = len(plane_names)
            in_specs = (
                [
                    pl.BlockSpec(memory_space=pltpu.SMEM),  # rows [T, L]
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
                    pl.BlockSpec(
                        (T, W), lambda g: (0, 0), memory_space=pltpu.SMEM
                    ),
                    pl.BlockSpec(
                        (T, W), lambda g: (0, 0), memory_space=pltpu.SMEM
                    ),
                ]
            )
            out_shapes = (
                [
                    jax.ShapeDtypeStruct((rows, LANE), jnp.int32)
                    for _ in plane_names
                ]
                + [
                    jax.ShapeDtypeStruct(
                        (ring_len + 1, rows, LANE), jnp.int32
                    )
                    for _ in plane_names
                ]
                + [
                    jax.ShapeDtypeStruct((T, W), jnp.int32),
                    jax.ShapeDtypeStruct((T, W), jnp.int32),
                ]
            )
            aliases = {3 + i: i for i in range(2 * n_p)}
            results = pl.pallas_call(
                kernel,
                grid=(self.n_tiles,),
                in_specs=in_specs,
                out_specs=out_specs,
                out_shape=out_shapes,
                input_output_aliases=aliases,
                compiler_params=(
                    None
                    if self.interpret
                    else pltpu.CompilerParams(
                        vmem_limit_bytes=100 * 1024 * 1024
                    )
                ),
                interpret=self.interpret,
            )(
                rows_i32,
                gi,
                owner,
                *[packed[n_] for n_ in plane_names],
                *[packed["r_" + n_] for n_ in plane_names],
            )
            outs = dict(zip(plane_names, results[: n_p]))
            outs.update(
                zip(["r_" + n_ for n_ in plane_names], results[n_p : 2 * n_p])
            )
            return outs, results[-2], results[-1]

        return run

    # -- scalar post-pass: frame fields, verify carry, returned checksums

    def _scalar_pass(self, ring_frame, state_frame, verify, rows, parts_hi,
                     parts_lo):
        """jnp mirror of _tick_impl's scalar behavior over the T x W save
        events: ring/state frame updates, the device-verify first-seen
        history, and the per-slot (hi, lo) outputs with their frame terms
        (zeros for skipped saves, exactly like the XLA path)."""
        core = self.core
        W, ring_len = self.W, self.ring_len
        off_save = core._off_save

        def row_body(carry, xs):
            ring_frame, state_frame, verify = carry
            row, p_hi, p_lo = xs
            do_load = row[0] != 0
            load_slot = row[1]
            advance_count = row[2]
            start_frame = row[3]
            # the state's OWN frame drives saved checksums and ring frame
            # fields (exactly what the XLA path's game.checksum(state)
            # reads); the verify history keys on start_frame + i, exactly
            # like _tick_impl's _verify_update call. Sessions keep the two
            # identical by construction; matching both independently makes
            # the backends bit-equal even for hand-driven streams.
            state_frame = jnp.where(
                do_load, ring_frame[load_slot], state_frame
            )
            his = []
            los = []
            for i in range(W):
                save_slot = row[off_save + i]
                do_save = save_slot < ring_len
                # state frame entering slot i: advances stop at
                # advance_count, exactly like the state itself (a save
                # past the last advance checksums the frozen state)
                frame_i = state_frame + jnp.minimum(i, advance_count)
                hi = jax.lax.bitcast_convert_type(
                    p_hi[i] + frame_i * self._cs_frame_weight, jnp.uint32
                )
                lo = jax.lax.bitcast_convert_type(
                    p_lo[i] + frame_i, jnp.uint32
                )
                hi = jnp.where(do_save, hi, jnp.uint32(0))
                lo = jnp.where(do_save, lo, jnp.uint32(0))
                his.append(hi)
                los.append(lo)
                wslot = jnp.where(do_save, save_slot, 0)
                ring_frame = ring_frame.at[wslot].set(
                    jnp.where(do_save, frame_i, ring_frame[wslot])
                )
                if core.device_verify:
                    upd = core._verify_update(
                        verify, start_frame + i, hi, lo
                    )
                    verify = jax.tree.map(
                        lambda new, old: jnp.where(do_save, new, old),
                        upd,
                        verify,
                    )
            state_frame = state_frame + advance_count
            return (ring_frame, state_frame, verify), (
                jnp.stack(his), jnp.stack(los),
            )

        (ring_frame, state_frame, verify), (his, los) = jax.lax.scan(
            row_body,
            (ring_frame, state_frame, verify),
            (rows, parts_hi, parts_lo),
        )
        return ring_frame, state_frame, verify, his, los

    # -- public ----------------------------------------------------------

    def run_kernel(self, ring, state, rows, gi_offset=0):
        """pack -> kernel -> (plane outs, partial checksums). `gi_offset`
        shifts the global entity-index plane to this kernel's slice of the
        world (the sharded composition's seam); the scalar post-pass is NOT
        applied — sharded callers psum the partials first."""
        T = rows.shape[0]
        run = self._run(int(T))
        packed = self.pack(ring, state)
        gi, owner = make_gi_owner(self.n_rows, self.num_players, gi_offset)
        return run(packed, rows.astype(jnp.int32), gi, owner)

    def tick_multi(self, ring, state, rows, verify):
        """Run T packed tick rows; returns (ring, state, verify, his[T,W],
        los[T,W]) with the same semantics as ResimCore._tick_multi_impl."""
        outs, parts_hi, parts_lo = self.run_kernel(ring, state, rows)
        new_ring, new_state = self.unpack(outs, ring, state)
        ring_frame, state_frame, verify, his, los = self._scalar_pass(
            ring["frame"],
            state["frame"],
            verify,
            rows.astype(jnp.int32),
            parts_hi,
            parts_lo,
        )
        new_ring["frame"] = ring_frame
        new_state["frame"] = state_frame
        return new_ring, new_state, verify, his, los


class ShardedPallasTickCore:
    """The entity-tiled tick kernel composed with a device mesh: shard_map
    over the `entity` axis runs one local kernel per device on its slice of
    the world + snapshot ring, psums the per-shard partial checksums (int32
    wraparound sums are order-invariant, so the totals are bit-identical to
    the unsharded kernel's), then runs the scalar post-pass on the
    replicated scalars. Drop-in for ResimCore's (ring, state, rows, verify)
    tick program under `mesh=` — the request path's multi-chip execution at
    the tiled kernel's bandwidth (completing for P2P/lazy ticks what
    ShardedPallasTiledCore did for the fused SyncTest batch)."""

    def __init__(self, core, mesh, interpret: bool = False):
        from ..parallel.sharded import entity_shardable

        self.mesh = mesh
        n_shards = mesh.shape.get("entity", 0)
        game = core.game
        assert getattr(get_adapter(game), "tileable", False), (
            "the sharded tick kernel needs a per-entity-independent "
            "(tileable) adapter: a reduction-phase adapter's full-plane "
            "sums would be silently local per shard; sharded reduce models "
            "run the XLA path (GSPMD inserts the psums)"
        )
        assert entity_shardable(game.num_entities, mesh, LANE), (
            f"num_entities {game.num_entities} must split into "
            f"{n_shards} 128-aligned shards over the mesh's `entity` axis"
        )
        self.local_n = game.num_entities // n_shards
        self.inner = PallasTickCore(
            core, interpret=interpret, local_entities=self.local_n
        )
        self.core = core

    def tick_multi(self, ring, state, rows, verify):
        from jax.sharding import PartitionSpec as P

        from ..parallel.sharded import ring_specs, state_specs

        inner = self.inner
        local_n = self.local_n
        s_specs = state_specs(state)
        r_specs = ring_specs(ring)
        verify_specs = jax.tree.map(lambda x: P(), verify)

        def body(ring, state, rows, verify):
            idx = jax.lax.axis_index("entity")
            offset = idx.astype(jnp.int32) * jnp.int32(local_n)
            outs, parts_hi, parts_lo = inner.run_kernel(
                ring, state, rows, offset
            )
            # the ONLY cross-shard collective in the hot loop: wraparound
            # partial-checksum sums ride ICI; everything else is local
            parts_hi = jax.lax.psum(parts_hi, "entity")
            parts_lo = jax.lax.psum(parts_lo, "entity")
            new_ring, new_state = inner.unpack(outs, ring, state)
            ring_frame, state_frame, verify, his, los = inner._scalar_pass(
                ring["frame"],
                state["frame"],
                verify,
                rows.astype(jnp.int32),
                parts_hi,
                parts_lo,
            )
            new_ring["frame"] = ring_frame
            new_state["frame"] = state_frame
            return new_ring, new_state, verify, his, los

        shard_fn = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(r_specs, s_specs, P(), verify_specs),
            out_specs=(r_specs, s_specs, verify_specs, P(), P()),
            # pallas outputs defeat replication inference; the replicated
            # outs (scalar-pass results) are computed identically on every
            # shard from replicated inputs (+psum'd totals)
            check_vma=False,
        )
        return shard_fn(ring, state, rows, verify)
