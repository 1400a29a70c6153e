"""Entity-tiled pallas kernel for the speculative beam rollout.

The beam's device cost is B x L full-world steps per tick. Under the XLA
vmap+scan path that work runs as dozens of unfused elementwise passes —
the same per-op overhead that makes the XLA SyncTest scan ~2% of HBM peak
— so speculation taxed ~15ms/tick on a 65k world (BENCH r3 exec phase),
swamping what adoption saves. This kernel runs the ENTIRE rollout as one
pallas program tiled over entities: each grid step streams one entity
tile's anchor state into VMEM and evaluates all B members x L steps on
it, writing the per-member per-frame trajectory planes and accumulating
per-(member, frame) partial checksums across tiles (SMEM revisit buffers,
exactly like pallas_tiled's save events). Legal for `tileable` adapters
(per-entity-independent step); the time/member-inside-tile order changes
nothing the model can observe.

Outputs are bit-identical to ResimCore._speculate_impl's XLA path — same
adapter math, same derived checksum weights, frame terms folded in the
post-pass — so adoption (which commits these trajectories into the ring)
is oblivious to which backend speculated.
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


class PallasBeamRollout:
    """Beam rollout executor for one (game, beam_width) pair; rollout
    length is a per-call compile key (the backend coalesces depths so only
    a handful of lengths ever compile)."""

    VMEM_TILE_BUDGET = 24 * 1024 * 1024

    def __init__(self, game, num_players: int, beam_width: int,
                 interpret: bool = False, tile_rows: int = 0,
                 max_rollout: int = 12, local_entities: int = 0):
        """`max_rollout`: the deepest rollout length the caller can
        request (ResimCore passes its window) — the VMEM tile budget is
        sized to it, so deep prediction windows get smaller tiles instead
        of silently oversubscribing the budget.

        `local_entities`: when nonzero, the kernel operates on that many
        entities (one shard's slice of the world) while checksum weights
        keep using the GLOBAL entity count — ShardedPallasBeamRollout
        runs one such local kernel per mesh device and psums the partial
        checksums, the same composition ShardedPallasTickCore uses."""
        self.n = local_entities or game.num_entities
        assert self.n % LANE == 0, "entity count must be 128-aligned"
        self.game = game
        self.adapter = get_adapter(game)
        tileable = getattr(self.adapter, "tileable", False)
        whole_world = not tileable
        if whole_world:
            # reduction-phase adapters (arena): single whole-world tile
            # only — the rollout's inline full-plane reductions must see
            # every entity (ResimCore falls back to XLA when rejected here)
            assert getattr(self.adapter, "reduce_len", 0) > 0, (
                f"{type(self.adapter).__name__} is neither tileable nor "
                "reduction-declaring; the XLA vmap rollout handles this model"
            )
            assert self.n == game.num_entities, (
                "reduction-phase adapters cannot run on a shard's slice "
                "(local sums would replace the global reduction)"
            )
        self.num_players = num_players
        self.input_size = game.input_size
        self.B = beam_width
        self.n_rows = self.n // LANE
        self.interpret = interpret
        n_planes = len(self.adapter.planes)
        # in: anchor planes; out: B*L trajectory windows per plane —
        # double-buffered by Mosaic
        per_row = n_planes * (1 + self.B * max_rollout) * LANE * 4 * 2
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
                f"B={self.B} x L={max_rollout} trajectory windows "
                f"(~{per_row * self.n_rows >> 20}MB) exceed the single-tile "
                "budget for a reduction-phase adapter"
            )
        assert self.n_rows % tile_rows == 0
        assert tile_rows >= 8 or tile_rows == self.n_rows
        self.tile_rows = tile_rows
        self.n_tiles = self.n_rows // tile_rows
        self._run = functools.lru_cache(maxsize=8)(self._build)
        self._cs_entries, self._cs_frame_weight = derive_checksum_weights(
            game, self.adapter
        )

    # -- packing ---------------------------------------------------------

    def pack_state(self, state) -> Dict[str, Any]:
        rows = self.n_rows
        packed = {}
        for name, key, c in self.adapter.planes:
            plane = state[key] if c is None else state[key][..., c]
            packed[name] = plane.reshape(rows, LANE)
        return packed

    def unpack_traj(self, outs, L: int, anchor_frame):
        """Trajectory planes [B*L, rows, LANE] -> state pytree with leaves
        [B, L, ...] (+ the scaffolding-managed frame leaf)."""
        n = self.n
        traj = rebuild_from_planes(
            plane_groups(self.adapter), lambda nm: outs[nm], (self.B, L), n
        )
        steps = jnp.arange(L, dtype=jnp.int32)[None, :]
        traj["frame"] = jnp.broadcast_to(
            anchor_frame.astype(jnp.int32) + 1 + steps, (self.B, L)
        )
        return traj

    # -- kernel ----------------------------------------------------------

    def _build(self, L: int):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        B, rows, tile_rows = self.B, self.n_rows, self.tile_rows
        P, I = self.num_players, self.input_size
        adapter = self.adapter
        plane_names = [name for name, _, _ in adapter.planes]
        n_tiles = self.n_tiles

        def kernel(inputs_ref, gi_ref, owner_ref, *refs):
            n_p = len(plane_names)
            anchors = dict(zip(plane_names, refs[:n_p]))
            trajs = dict(zip(plane_names, refs[n_p : 2 * n_p]))
            parts_hi_ref = refs[2 * n_p]
            parts_lo_ref = refs[2 * n_p + 1]

            first_tile = pl.program_id(0) == 0
            ctx = KernelCtx(gi_ref[:], owner_ref[:])

            def partial_checksum(state):
                return partial_checksum_planes(self._cs_entries, ctx.gi, state)

            anchor = {n_: anchors[n_][:] for n_ in plane_names}
            for b in range(B):
                state = anchor
                for l in range(L):
                    inps = [
                        [inputs_ref[b, l, p * I + j] for j in range(I)]
                        for p in range(P)
                    ]
                    state = adapter.step(state, inps, ctx)
                    for n_ in plane_names:
                        trajs[n_][pl.ds(b * L + l, 1)] = state[n_][None]
                    hi, lo = partial_checksum(state)
                    base_hi = jnp.where(
                        first_tile, jnp.int32(0), parts_hi_ref[b, l]
                    )
                    base_lo = jnp.where(
                        first_tile, jnp.int32(0), parts_lo_ref[b, l]
                    )
                    parts_hi_ref[b, l] = base_hi + hi
                    parts_lo_ref[b, l] = base_lo + lo

        def state_spec():
            return pl.BlockSpec(
                (tile_rows, LANE), lambda g: (g, 0), memory_space=pltpu.VMEM
            )

        def traj_spec():
            return pl.BlockSpec(
                (B * L, tile_rows, LANE),
                lambda g: (0, g, 0),
                memory_space=pltpu.VMEM,
            )

        def run(packed, inputs_i32, gi, owner):
            in_specs = (
                [
                    pl.BlockSpec(memory_space=pltpu.SMEM),  # inputs [B,L,P*I]
                    state_spec(),  # gi
                    state_spec(),  # owner
                ]
                + [state_spec() for _ in plane_names]
            )
            out_specs = [traj_spec() for _ in plane_names] + [
                # cross-tile checksum accumulators (every grid step maps to
                # the same block, so partial sums carry across tiles)
                pl.BlockSpec(
                    (B, L), lambda g: (0, 0), memory_space=pltpu.SMEM
                ),
                pl.BlockSpec(
                    (B, L), lambda g: (0, 0), memory_space=pltpu.SMEM
                ),
            ]
            out_shapes = [
                jax.ShapeDtypeStruct((B * L, rows, LANE), jnp.int32)
                for _ in plane_names
            ] + [
                jax.ShapeDtypeStruct((B, L), jnp.int32),
                jax.ShapeDtypeStruct((B, L), jnp.int32),
            ]
            results = pl.pallas_call(
                kernel,
                grid=(n_tiles,),
                in_specs=in_specs,
                out_specs=out_specs,
                out_shape=out_shapes,
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
                gi,
                owner,
                *[packed[n_] for n_ in plane_names],
            )
            outs = dict(zip(plane_names, results[: len(plane_names)]))
            return outs, results[-2], results[-1]

        return run

    # -- public ----------------------------------------------------------

    def run_kernel(self, anchor_state, beam_inputs, gi_offset=0):
        """pack -> kernel -> (plane outs, partial checksums). `gi_offset`
        shifts the global entity-index plane to this kernel's slice of
        the world (the sharded composition's seam); the frame fold is NOT
        applied — sharded callers psum the partials first."""
        B, L = beam_inputs.shape[0], beam_inputs.shape[1]
        assert B == self.B
        run = self._run(int(L))
        packed = self.pack_state(anchor_state)
        inputs_i32 = beam_inputs.reshape(
            B, L, self.num_players * self.input_size
        ).astype(jnp.int32)
        gi, owner = make_gi_owner(self.n_rows, self.num_players, gi_offset)
        return run(packed, inputs_i32, gi, owner)

    def finish(self, outs, parts_hi, parts_lo, anchor_frame, L: int):
        """Fold the frame checksum terms (once per member x step, exactly
        like the XLA path's game.checksum of the stepped state) and
        rebuild the trajectory pytree. Sharded callers pass psum'd
        partials; the fold then matches the unsharded totals bit-for-bit."""
        steps = jnp.arange(L, dtype=jnp.int32)[None, :]
        frames = anchor_frame.astype(jnp.int32) + 1 + steps
        his = jax.lax.bitcast_convert_type(
            parts_hi + frames * self._cs_frame_weight, jnp.uint32
        )
        los = jax.lax.bitcast_convert_type(parts_lo + frames, jnp.uint32)
        traj = self.unpack_traj(outs, L, anchor_frame)
        return traj, his, los

    def rollout(self, anchor_state, beam_inputs):
        """anchor_state: the game-state pytree at the anchor frame;
        beam_inputs: u8[B, L, P, I]. Returns (traj pytree [B, L, ...],
        his u32[B, L], los u32[B, L]) bit-identical to the XLA vmap+scan
        rollout under all-CONFIRMED statuses."""
        outs, parts_hi, parts_lo = self.run_kernel(anchor_state, beam_inputs)
        return self.finish(
            outs, parts_hi, parts_lo, anchor_state["frame"],
            int(beam_inputs.shape[1]),
        )


class ShardedPallasBeamRollout:
    """The entity-tiled beam rollout composed with a device mesh: one
    LOCAL kernel per device over the `entity` axis (each device rolls out
    every beam member on its slice of the world — the beam axis needs no
    collective), per-(member, frame) partial checksums psum'd across
    shards (int32 wraparound sums are order-invariant, so the totals are
    bit-identical to the unsharded kernel's). Exactly the
    ShardedPallasTickCore recipe applied to speculation — the flagship
    sharded config then speculates at the fused kernel's cost instead of
    the unfused XLA vmap+scan's (the restriction VERDICT r4 flagged at
    resim.py:204-207). The adopted trajectory keeps its entity sharding,
    so the (XLA) adopt dispatch consumes it in place under GSPMD."""

    def __init__(self, game, num_players: int, beam_width: int, mesh,
                 interpret: bool = False, max_rollout: int = 12):
        from ..parallel.sharded import entity_shardable

        self.mesh = mesh
        n_shards = mesh.shape.get("entity", 0)
        assert getattr(get_adapter(game), "tileable", False), (
            "the sharded beam rollout needs a per-entity-independent "
            "(tileable) adapter: a reduction-phase adapter's full-plane "
            "sums would be silently local per shard; sharded reduce "
            "models speculate via the XLA path (GSPMD inserts the psums)"
        )
        assert entity_shardable(game.num_entities, mesh, LANE), (
            f"num_entities {game.num_entities} must split into "
            f"{n_shards} 128-aligned shards over the mesh's `entity` axis"
        )
        self.local_n = game.num_entities // n_shards
        self.inner = PallasBeamRollout(
            game, num_players, beam_width,
            interpret=interpret, max_rollout=max_rollout,
            local_entities=self.local_n,
        )
        self.game = game

    def rollout(self, anchor_state, beam_inputs):
        from jax.sharding import PartitionSpec as P

        from ..parallel.sharded import state_specs

        inner = self.inner
        local_n = self.local_n
        L = int(beam_inputs.shape[1])
        s_specs = state_specs(anchor_state)
        # trajectory leaves carry a leading [B, L] over each state leaf;
        # the frame leaf ([B, L], built from the replicated anchor frame)
        # is replicated
        t_specs = jax.tree.map(
            lambda x: P(None, None, "entity") if x.ndim >= 1 else P(),
            anchor_state,
        )

        def body(anchor, inputs):
            idx = jax.lax.axis_index("entity")
            offset = idx.astype(jnp.int32) * jnp.int32(local_n)
            outs, parts_hi, parts_lo = inner.run_kernel(
                anchor, inputs, offset
            )
            # the ONLY cross-shard collective: wraparound partial-checksum
            # sums ride ICI; the rollout itself is embarrassingly local
            parts_hi = jax.lax.psum(parts_hi, "entity")
            parts_lo = jax.lax.psum(parts_lo, "entity")
            return inner.finish(outs, parts_hi, parts_lo, anchor["frame"], L)

        shard_fn = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(s_specs, P()),
            out_specs=(t_specs, P(), P()),
            # pallas outputs defeat replication inference; the replicated
            # outs (checksums) are computed identically on every shard
            # from psum'd totals
            check_vma=False,
        )
        return shard_fn(anchor_state, beam_inputs)
