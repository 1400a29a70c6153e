"""Sharded-world rollback: entity-sharded state + beam-sharded speculation
over a device mesh, with the checksum as an explicit cross-shard psum.

This is the multi-chip configuration (BASELINE.json configs[4]: 64k-component
state over 4 chips with a psum checksum): the world's SoA arrays are sharded
over the `entity` mesh axis, candidate input futures over the `beam` axis.
The step function itself is embarrassingly parallel over entities (no
cross-entity interactions in the flagship model), so the only collective in
the hot loop is the checksum reduction — exactly the shape that rides ICI
well. GSPMD partitions the jitted scan from the input shardings; the
checksum's cross-shard sum is additionally expressed explicitly with
shard_map + psum in `sharded_checksum` for the desync-detection path.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.fixed_point import GOLDEN32

def state_specs(state):
    """PartitionSpec pytree for a game-state pytree: entity arrays split
    over the `entity` axis on axis 0, scalars replicated. THE sharded-state
    placement policy as specs — shard_state places with it, and every
    shard_map consumer (ShardedPallasTiledCore, ShardedPallasTickCore)
    must build its in/out specs from here so the contract can't drift."""
    return jax.tree.map(lambda x: P("entity") if x.ndim >= 1 else P(), state)


def ring_specs(ring):
    """PartitionSpec pytree for a snapshot-ring pytree (state leaves with a
    leading slot axis): entity dims split over `entity` on axis 1, per-slot
    scalars replicated. The ring twin of `state_specs`."""
    return jax.tree.map(
        lambda x: P(None, "entity") if x.ndim >= 2 else P(), ring
    )


def shard_state(state, mesh: Mesh):
    """Place a game-state pytree on the mesh per `state_specs` (every
    consumer — ResimCore, TpuSyncTestSession, the beam rollout — must route
    through here or `shard_ring` so the contract can't drift between
    components): every non-scalar state leaf has entities on axis 0,
    divisible by the `entity` axis size."""
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        state,
        state_specs(state),
    )


def shard_ring(ring, mesh: Mesh):
    """Place a snapshot-ring pytree on the mesh per `ring_specs` — the
    ring twin of `shard_state`."""
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        ring,
        ring_specs(ring),
    )


def entity_shardable(num_entities: int, mesh: Mesh, lane: int = 128) -> bool:
    """THE divisibility rule for running one local entity-tiled pallas
    kernel per mesh device: the world must split into `entity`-axis shards
    of 128-lane-aligned size. Shared by ResimCore's backend auto-selection
    and the sharded cores' constructor asserts so the two can't drift."""
    if "entity" not in mesh.axis_names:
        return False
    return num_entities % (mesh.shape["entity"] * lane) == 0


def sharded_checksum(state, mesh: Mesh, keys=None):
    """Order-invariant checksum of an entity-sharded state with an explicit
    psum across the `entity` axis (the on-device replacement for the
    reference's host-side fletcher16, ex_game.rs:42-52).

    Bit-identical to the single-device `_checksum_generic`: word weights run
    continuously across the model's concatenation order `keys` + frame
    using GLOBAL word indices, and the replicated `frame` scalar is folded
    in exactly once (on entity-shard 0) — so a sharded peer and a
    single-chip peer exchanging desync-detection reports always agree.
    `keys` must be the model's declared checksum order (its
    `checksum_keys` class attribute, e.g. ExGame.checksum_keys — the same
    source _checksum_generic reads); defaults to ex_game's.
    """
    if keys is None:
        from ..models.ex_game import CHECKSUM_KEYS as keys
    keys = list(keys)
    offsets = {}
    off = 0
    for k in keys:
        offsets[k] = off
        off += int(np.prod(state[k].shape))
    frame_offset = off

    entity_state = {k: state[k] for k in keys}
    flat_specs = {k: P("entity") for k in keys}

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(flat_specs, P()),
        out_specs=(P(), P()),
    )
    def _cs(local_state, frame):
        idx = jax.lax.axis_index("entity")
        hi = jnp.uint32(0)
        lo = jnp.uint32(0)
        for k in keys:
            # axis-0 sharding + row-major flatten => shard s owns the
            # contiguous global word range [s*n_local, (s+1)*n_local)
            words = local_state[k].astype(jnp.uint32).reshape(-1)
            n_local = words.shape[0]
            start = jnp.uint32(offsets[k]) + idx.astype(jnp.uint32) * jnp.uint32(n_local)
            gidx = jnp.arange(n_local, dtype=jnp.uint32) + start + jnp.uint32(1)
            hi = hi + jnp.sum(words * (gidx * GOLDEN32), dtype=jnp.uint32)
            lo = lo + jnp.sum(words, dtype=jnp.uint32)
        # frame is replicated: fold it in on one shard only
        fw = frame.astype(jnp.uint32)
        fg = jnp.uint32(frame_offset + 1)
        on_shard0 = (idx == 0).astype(jnp.uint32)
        hi = hi + on_shard0 * (fw * (fg * GOLDEN32))
        lo = lo + on_shard0 * fw
        hi = jax.lax.psum(hi, "entity")
        lo = jax.lax.psum(lo, "entity")
        return hi, lo

    return _cs(entity_state, state["frame"])


# ---------------------------------------------------------------------------
# stacked (serving) placement: the session axis of MultiSessionDeviceCore's
# stacked pytrees split over a `session` mesh axis, entity arrays optionally
# split further over `entity`. THE placement policy for the sharded serving
# core — ShardedMultiSessionDeviceCore places with these specs and every
# consumer (host scheduler affinity, the explicit checksum pass, tests)
# derives shard geometry from the same functions so the contract can't
# drift from the single-world policy above.
# ---------------------------------------------------------------------------


def _mesh_has_entity(mesh: Mesh) -> bool:
    return "entity" in mesh.axis_names and mesh.shape["entity"] > 1


def stacked_state_specs(stacked_state, mesh: Mesh):
    """PartitionSpec pytree for a STACKED game-state pytree (leading
    session axis on every leaf): sessions split over `session` on axis 0;
    entity arrays (ndim >= 2) additionally split over `entity` on axis 1
    when the mesh carries one. The serving twin of `state_specs`."""
    ent = _mesh_has_entity(mesh)
    return jax.tree.map(
        lambda x: P("session", "entity") if ent and x.ndim >= 2 else P("session"),
        stacked_state,
    )


def stacked_ring_specs(stacked_ring, mesh: Mesh):
    """PartitionSpec pytree for a STACKED snapshot-ring pytree (leading
    session axis, then the ring-slot axis): sessions over `session`,
    entity dims (ndim >= 3) over `entity` on axis 2, ring slots always
    local. The serving twin of `ring_specs`."""
    ent = _mesh_has_entity(mesh)
    return jax.tree.map(
        lambda x: (
            P("session", None, "entity") if ent and x.ndim >= 3 else P("session")
        ),
        stacked_ring,
    )


def shard_stacked_state(stacked_state, mesh: Mesh):
    """Place a stacked game-state pytree on the mesh per
    `stacked_state_specs`. The leading (session) axis must divide the
    `session` axis size — the sharded core pads its dummy-slot tail so
    it does."""
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        stacked_state,
        stacked_state_specs(stacked_state, mesh),
    )


def shard_stacked_ring(stacked_ring, mesh: Mesh):
    """Place a stacked snapshot-ring pytree on the mesh per
    `stacked_ring_specs` — the ring twin of `shard_stacked_state`."""
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        stacked_ring,
        stacked_ring_specs(stacked_ring, mesh),
    )


def mailbox_spec() -> P:
    """Partition spec for the device-resident input mailbox's [S, K, L]
    row ring (and its [S] watermark vector): the slot axis splits over
    the mesh's `session` axis, virtual-tick and control-word axes stay
    local — a lane's whole fill cycle lives with the shard that owns its
    world, so the resident driver's per-vtick row reads never cross
    ICI."""
    return P("session")


def shard_mailbox(rows, mesh: Mesh):
    """Place a mailbox row ring (or watermark vector) on the mesh per
    `mailbox_spec` — the resident-loop twin of `shard_stacked_state`."""
    return jax.device_put(rows, NamedSharding(mesh, mailbox_spec()))


def stacked_sharded_checksum(stacked_state, mesh: Mesh, keys=None):
    """Per-slot order-invariant checksums of a session-stacked (and
    optionally entity-sharded) state pytree, with the cross-shard word
    reduction expressed EXPLICITLY as shard_map + psum over the `entity`
    axis — the stacked twin of `sharded_checksum`, and the serving
    core's desync-detection spot-check for big entity-sharded worlds
    (the megabatch programs' own [B, W] checksums ride the same
    concat-free partial sums under GSPMD; this pass pins the collective
    shape by hand so a partitioner regression is caught against it).

    Returns (hi[S], lo[S]) uint32 arrays, slot-aligned with the stack.
    Bit-identical to vmapping the model's `_checksum_generic` over the
    slots: word weights run continuously across `keys` + frame with
    GLOBAL word indices, and the replicated `frame` scalar folds in
    exactly once (on entity-shard 0). `keys` defaults to ex_game's
    declared checksum order."""
    if keys is None:
        from ..models.ex_game import CHECKSUM_KEYS as keys
    keys = list(keys)
    ent = _mesh_has_entity(mesh)
    offsets = {}
    off = 0
    for k in keys:
        offsets[k] = off
        off += int(np.prod(stacked_state[k].shape[1:]))
    frame_offset = off

    entity_state = {k: stacked_state[k] for k in keys}
    in_state_specs = {
        k: (P("session", "entity") if ent else P("session")) for k in keys
    }

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(in_state_specs, P("session")),
        out_specs=(P("session"), P("session")),
    )
    def _cs(local_state, frames):
        eidx = jax.lax.axis_index("entity") if ent else jnp.uint32(0)
        s_loc = frames.shape[0]
        hi = jnp.zeros((s_loc,), jnp.uint32)
        lo = jnp.zeros((s_loc,), jnp.uint32)
        for k in keys:
            # entity axis-0-of-the-slot sharding + row-major flatten =>
            # entity shard e owns the contiguous per-slot word range
            # [e * n_local, (e + 1) * n_local) of this key
            words = local_state[k].astype(jnp.uint32).reshape(s_loc, -1)
            n_local = words.shape[1]
            start = (
                jnp.uint32(offsets[k])
                + eidx.astype(jnp.uint32) * jnp.uint32(n_local)
            )
            gidx = jnp.arange(n_local, dtype=jnp.uint32) + start + jnp.uint32(1)
            hi = hi + jnp.sum(
                words * (gidx * GOLDEN32)[None, :], axis=1, dtype=jnp.uint32
            )
            lo = lo + jnp.sum(words, axis=1, dtype=jnp.uint32)
        # frame is replicated across entity shards: fold in on shard 0 only
        fw = frames.astype(jnp.uint32)
        fg = jnp.uint32(frame_offset + 1)
        on_shard0 = (eidx == 0).astype(jnp.uint32)
        hi = hi + on_shard0 * (fw * (fg * GOLDEN32))
        lo = lo + on_shard0 * fw
        if ent:
            hi = jax.lax.psum(hi, "entity")
            lo = jax.lax.psum(lo, "entity")
        return hi, lo

    return _cs(entity_state, stacked_state["frame"])


def make_sharded_beam_rollout(game, mesh: Mesh, window: int):
    """jit-compiled W-frame beam rollout over a (beam x entity) mesh.

    state: entity-sharded pytree (replicated across beam)
    beam_inputs u8[B, W, P, I], beam_statuses i32[B, W, P]: beam-sharded
    returns final states [B, ...] (beam x entity sharded) and per-beam
    checksums (via GSPMD-partitioned reduction).
    """

    def rollout_one(state, inputs, statuses):
        def body(s, xs):
            inp, stat = xs
            return game.step(s, inp, stat), None

        final, _ = jax.lax.scan(body, state, (inputs, statuses))
        hi, lo = game.checksum(final)
        return final, hi, lo

    vmapped = jax.vmap(rollout_one, in_axes=(None, 0, 0))
    beam_sharding = NamedSharding(mesh, P("beam"))

    @jax.jit
    def run(state, beam_inputs, beam_statuses):
        beam_inputs = jax.lax.with_sharding_constraint(beam_inputs, beam_sharding)
        beam_statuses = jax.lax.with_sharding_constraint(beam_statuses, beam_sharding)
        return vmapped(state, beam_inputs, beam_statuses)

    return run
