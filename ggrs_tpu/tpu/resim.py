"""The rollback hot loop as one compiled device program.

The reference's rollback driver crosses the user boundary up to
max_prediction times per tick — load a snapshot, then N x (save + advance)
callbacks (src/sessions/p2p_session.rs:649-670). On TPU that many
host<->device round trips would dwarf the math, so the entire block is one
jit-compiled `lax.scan` over a device-resident snapshot ring:

- the ring is a pytree of [R+1, ...] arrays, R = max_prediction + 2 (the
  same capacity/addressing as the host SyncLayer ring,
  src/sync_layer.rs:61-75); slot R is a scratch slot that masked-off saves
  write into, so the scan stays branch-free.
- one tick = optional load (dynamic ring index) + W fused
  (save?, advance?) micro-slots, W = max_prediction + 2, with rollback
  depth and save slots as traced scalars — a single compilation covers
  every depth.
- the per-save checksum is computed on device in the same scan.

Buffers are donated, so the ring is updated in place across ticks.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import ContractViolation

from ..obs import DISPATCH_DEPTH_BUCKETS, GLOBAL_TELEMETRY


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def depth_dispatch_instruments():
    """The two depth-adaptive-dispatch instruments, get-or-created on the
    global registry: a histogram of the routed depth bucket (window slots
    actually executed) per dispatch, and a counter of full-window slots
    minus the slots actually dispatched — the device work depth routing
    avoided. Shared by every routed path (T=1 branchless variants, the
    lazy multi-tick scan, the cross-session megabatch): one pair of
    series makes the win — and a silent routing regression (waste
    flatlining at 0, depth pinned at the window) — visible in any
    telemetry snapshot."""
    reg = GLOBAL_TELEMETRY.registry
    depth = reg.histogram(
        "ggrs_dispatch_depth",
        "window slots actually executed by a depth-routed device dispatch",
        buckets=DISPATCH_DEPTH_BUCKETS,
    )
    waste = reg.counter(
        "ggrs_padded_slot_waste",
        "full-window slots minus active slots actually dispatched "
        "(device work avoided by depth-adaptive dispatch)",
    )
    return depth, waste


class ResimCore:
    """Device snapshot ring + fused (load, resimulate, save, checksum) tick.

    `game` implements the DeviceGame interface: init_state() -> pytree,
    step(state, inputs u8[P, input_size], statuses i32[P]) -> pytree,
    checksum(state) -> (u32, u32). All pure jax.
    """

    # worlds up to this size route lone ticks through the branchless
    # unrolled program (see the _tick_fn comment in __init__): ~0.5ms of
    # worst-case masked work buys ~2ms of control-flow dispatch overhead
    BRANCHLESS_MAX_ENTITIES = 1 << 18
    # trivial T=1 rows (no load, one advance) route through the WINDOWED
    # cond program from this world size up: below it the full cond
    # program's skipped slots cost too little device time to buy the
    # extra per-core compile (every interactive session would pay a
    # compile for a program that saves microseconds on toy worlds)
    T1_WINDOWED_MIN_ENTITIES = 1 << 11
    # worlds at or past this size route lone ticks through the pallas
    # tick kernel (as a 1-row multi dispatch) when the core has one: the
    # XLA T=1 programs run the step as unfused elementwise passes whose
    # cost grows with the world, while the kernel streams state+ring
    # through VMEM once. Crossover measured on a v5e reached through a
    # remote-device layer, not yet re-measured on an attached chip
    # (chained dispatch, one barrier): 65k entities XLA-branchless 7.8ms vs
    # kernel 8.9ms; 262k XLA-branchless 19.5ms / XLA-cond 33.1ms vs
    # kernel 9.9ms — the kernel's cost is nearly size-flat, so route
    # everything from 128k up (including worlds past the branchless cap,
    # which previously fell back to the cond program).
    PALLAS_T1_MIN_ENTITIES = 1 << 17

    def __init__(self, game, max_prediction: int, num_players: int, mesh=None,
                 device_verify: bool = False, spec_backend: str = "auto",
                 tick_backend: str = "auto"):
        """`mesh`: optional jax Mesh with an `entity` axis — the live state
        AND the snapshot ring shard across it (BASELINE.json configs[4]), so
        a partitioned world can run inside any session that drives this
        core (the seam the reference exposes at
        src/sessions/p2p_session.rs:621-673, here executed multi-chip).
        GSPMD partitions the fused tick from the operand shardings; the
        checksum reduction is the only cross-shard collective (uint32
        wraparound sums are order-invariant, so the psum'd value is
        bit-identical to the single-chip one). Sharded-state contract: every
        non-scalar state leaf has entities on axis 0, divisible by the
        `entity` axis size. If the mesh also has a `beam` axis, speculative
        rollouts shard candidate futures across it."""
        self.game = game
        self.num_players = num_players
        self.max_prediction = max_prediction
        self.ring_len = max_prediction + 2  # parity with SavedStates
        self.scratch_slot = self.ring_len  # masked-off saves land here
        self.window = max_prediction + 2  # advances + possible trailing save
        self.mesh = mesh

        state = game.init_state()
        self._beam_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.sharded import shard_state

            assert "entity" in mesh.axis_names, "mesh needs an `entity` axis"
            state = shard_state(state, mesh)
            if "beam" in mesh.axis_names and mesh.shape["beam"] > 1:
                self._beam_sharding = NamedSharding(mesh, P("beam"))
        self.state = state
        ring = jax.tree.map(
            lambda x: jnp.zeros((self.ring_len + 1,) + x.shape, x.dtype), state
        )
        if mesh is not None:
            from ..parallel.sharded import shard_ring

            ring = shard_ring(ring, mesh)
        self.ring = ring
        # device-resident determinism verdict (opt-in): a first-seen
        # checksum history + mismatch latch updated INSIDE the fused tick,
        # mirroring the fused SyncTest session's _save_and_check. With it,
        # SyncTest-style verification needs NO per-burst host readback of
        # checksum values — every readback is a host/device round trip,
        # which would dominate the whole interactive path.
        # Only valid for confirmed-input replay (SyncTest): P2P rollbacks
        # legitimately re-save corrected frames with different state.
        self.device_verify = device_verify
        if device_verify:
            verify = {
                "h_tag": jnp.full((self.ring_len,), -1, dtype=jnp.int32),
                "h_hi": jnp.zeros((self.ring_len,), dtype=jnp.uint32),
                "h_lo": jnp.zeros((self.ring_len,), dtype=jnp.uint32),
                # [mismatch?, first mismatching frame]
                "flag": jnp.array([0, -1], dtype=jnp.int32),
            }
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                verify = jax.tree.map(
                    lambda x: jax.device_put(x, NamedSharding(mesh, P())),
                    verify,
                )
            self.verify = verify
        else:
            self.verify = {}
        # The T=1 interactive programs. lax.cond/lax.scan control flow
        # costs per-dispatch overhead EVEN WHEN THE TAKEN WORK IS TINY
        # (measured on a remote device in an earlier round, not yet on an
        # attached chip: a scan-of-conds program
        # with trivial compute dispatches at ~3.0ms vs ~1.5ms for the same
        # I/O branchless) — but cond SKIPPING also genuinely saves device
        # work when most of the window is skipped. So lone ticks route by
        # ROW CONTENT (host-side, both programs compiled): rollback /
        # multi-advance rows — which execute most of the window anyway —
        # run the fully UNROLLED, jnp.where-MASKED program (measured
        # 3.8 -> 1.5ms for an 8-frame rollback tick at 4k entities,
        # interleaved in one quiet window); trivial rows (one
        # advance, no load) keep the cond program, whose 14-of-15-slot
        # skip beats the masked full window (measured ~1.2ms the other
        # way, same methodology — bench dispatch_floor carries both).
        # Bit-identical either way: masked saves write the OLD value back
        # to slot 0, so even the ring's scratch bytes match. Worlds past
        # BRANCHLESS_MAX_ENTITIES always run cond (masked work there is
        # real bandwidth).
        n_entities = getattr(game, "num_entities", None)
        self._tick_fn = jax.jit(
            self._tick_packed_impl, donate_argnums=(0, 1, 3)
        )
        # the windowed cond program: the same per-slot cond tick truncated
        # to a STATIC nslots. Trivial T=1 rows (no load, one advance — the
        # speculative ticks between rollbacks, the dominant interactive
        # traffic) keep cond's taken-branch economics but stop paying the
        # full window's scanned slots of control flow: their last active
        # slot is <= 2, so they dispatch the smallest variant instead of
        # W slots of cond skipping. Bit-identical to the full cond
        # program (truncated slots are provably inert).
        self._tick_windowed_fn = jax.jit(
            self._tick_windowed_impl, static_argnums=(4,),
            donate_argnums=(0, 1, 3),
        )
        # nslots is a STATIC jit key: one executable per coalesced
        # depth variant (branchless_variants), all compiled by warmup
        self._tick_branchless_fn = (
            jax.jit(
                self._tick_branchless_impl,
                static_argnums=(4,),
                donate_argnums=(0, 1, 3),
            )
            if n_entities is not None
            and n_entities <= self.BRANCHLESS_MAX_ENTITIES
            else None
        )
        # nslots is a STATIC jit key here too: the lazy multi-tick scan
        # compiles one body per coalesced depth variant (the same
        # branchless_variants family as T=1), and the backend routes a
        # buffered batch by the MAX last-active slot across its rows —
        # a buffer of zero-rollback ticks scans 3 slots per row instead
        # of the full window (depth-adaptive dispatch)
        self._tick_multi_fn = jax.jit(
            self._tick_multi_impl, static_argnums=(4,),
            donate_argnums=(0, 1, 3),
        )
        # trivial-row windowed-cond routing gate (see the constants above)
        self._t1_windowed = (
            self._tick_branchless_fn is not None
            and n_entities is not None
            and n_entities >= self.T1_WINDOWED_MIN_ENTITIES
        )
        self._speculate_fn = jax.jit(self._speculate_impl)

        def pallas_eligible(extra=lambda: True, allow_mesh=False,
                            whole_world_fits=None) -> bool:
            """Can this (game, mesh) run a pallas kernel? THE one
            eligibility predicate for both the speculation and tick
            backends — a drifted copy would send them down different paths
            for the same game. `allow_mesh`: both the tick kernel and the
            beam rollout compose with a mesh (ShardedPallasTickCore /
            ShardedPallasBeamRollout shard_map local kernels + psum
            checksum partials) for tileable adapters.
            `whole_world_fits`: for reduction-phase adapters (arena) —
            non-tileable but runnable as ONE whole-world VMEM tile,
            unsharded only — the backend's single-tile sizing predicate
            (None = that backend resolves reduce models at dispatch)."""
            if jax.devices()[0].platform != "tpu":
                return False
            if mesh is not None:
                from ..parallel.sharded import entity_shardable

                if not allow_mesh or not entity_shardable(
                    game.num_entities, mesh
                ):
                    return False
            try:
                from .pallas_core import get_adapter

                # same rejection classes _pick_backend honors: KeyError =
                # no adapter registered; AssertionError/ValueError = a
                # model-envelope bound (e.g. arena's centroid-division
                # contract) — all mean "this config runs XLA", never a
                # construction-time crash
                adapter = get_adapter(game)
            except (KeyError, AssertionError, ValueError):
                return False
            if game.num_entities % 128 != 0 or not extra():
                return False
            if getattr(adapter, "tileable", False):
                return True
            if (
                mesh is not None
                or getattr(adapter, "reduce_len", 0) <= 0
                or whole_world_fits is None
            ):
                return False
            return whole_world_fits()

        # speculation backend: the XLA vmap+scan rollout runs the step as
        # unfused elementwise passes, so B*L speculative steps tax several
        # ms of device time per tick on mid-size worlds; the entity-tiled
        # pallas rollout (pallas_beam.py) runs the same math at the fused
        # kernel's cost for tileable models. "auto" picks pallas when the
        # model supports it (falling back to XLA otherwise); results are
        # bit-identical either way (tests enforce it).
        assert spec_backend in ("auto", "xla", "pallas", "pallas-interpret")
        if spec_backend == "auto":
            # reduce-phase adapters (arena): beam width is only known at
            # speculate time, so single-tile sizing resolves at dispatch —
            # _speculate_pallas falls back to XLA if the rollout rejects.
            # Under a mesh, tileable models run ShardedPallasBeamRollout
            # (one local kernel per device over the `entity` axis, psum'd
            # checksum partials); reduce models keep the XLA path, whose
            # GSPMD-inserted psums handle their global sums.
            spec_backend = (
                "pallas"
                if pallas_eligible(
                    allow_mesh=True, whole_world_fits=lambda: True
                )
                else "xla"
            )
        self.spec_backend = spec_backend
        self._beam_rollouts = {}  # beam_width -> PallasBeamRollout
        self._speculate_pallas_fns = {}  # beam_width -> jitted wrapper
        # tick backend: the generic control-word tick (and the lazy
        # multi-tick buffer) can run on the entity-tiled pallas kernel
        # for tileable models declaring a disconnect_input row —
        # bit-identical to the XLA scan (tests enforce it), at the fused
        # kernel's device cost instead of unfused per-op overhead. Under a
        # mesh the kernel composes via ShardedPallasTickCore (one local
        # kernel per device, psum'd checksum partials).
        assert tick_backend in ("auto", "xla", "pallas", "pallas-interpret")
        if tick_backend == "auto":
            from .pallas_resim import PallasTickCore

            tick_backend = (
                "pallas"
                if pallas_eligible(
                    lambda: getattr(game, "disconnect_input", None) is not None
                    and len(game.disconnect_input) == game.input_size,
                    allow_mesh=True,
                    whole_world_fits=lambda: PallasTickCore.whole_world_fits(
                        game, self.ring_len
                    ),
                )
                else "xla"
            )
        self.tick_backend = tick_backend
        if tick_backend.startswith("pallas"):
            interpret = tick_backend.endswith("-interpret")
            if mesh is not None:
                from .pallas_resim import ShardedPallasTickCore

                core = ShardedPallasTickCore(self, mesh, interpret=interpret)
            else:
                from .pallas_resim import PallasTickCore

                core = PallasTickCore(self, interpret=interpret)
            self._tick_pallas_fn = jax.jit(
                core.tick_multi, donate_argnums=(0, 1, 3)
            )
        else:
            self._tick_pallas_fn = None
        self._adopt_fn = jax.jit(self._adopt_impl, donate_argnums=(0, 6))
        # FULL-hit adoption is pure data movement: every corrected frame
        # is served from the precomputed trajectory, so the program is
        # selects + masked ring writes + the speculation's checksums — no
        # game.step, no checksum math, no control flow. The cond/scan
        # adopt program costs ~2x the branchless dispatch floor (the
        # same overhead _tick_branchless_impl exists to
        # avoid) AND reruns nothing, so on full hits the unrolled program
        # is strictly cheaper; partial hits keep the cond program (their
        # suffix genuinely resimulates, and masking W steps would cost
        # more than cond's skip). Same entity-count gate as the
        # branchless tick: past it the masked gathers are real bandwidth.
        self._adopt_full_fn = (
            jax.jit(self._adopt_full_impl, donate_argnums=(0, 6))
            if n_entities is not None
            and n_entities <= self.BRANCHLESS_MAX_ENTITIES
            else None
        )
        # tick's packed control-word layout (pack site: tick(); unpack:
        # _tick_packed_impl): 4 header words (do_load, load_slot,
        # advance_count, start_frame), then save_slots[W], statuses[W*P],
        # inputs[W*P*I]. The adopt path has its OWN layout — 6 header
        # words (member, load_slot, advance_count, shift, load_frame,
        # matched), then save_slots[W], statuses[W*P], inputs[W*P*I] (the
        # suffix resim rows) — see adopt()/_adopt_impl.
        p, i = num_players, game.input_size
        self._off_save = 4
        self._off_status = self._off_save + self.window
        self._off_input = self._off_status + self.window * p
        self._packed_len = self._off_input + self.window * p * i
        self._aoff_save = 6
        self._aoff_status = self._aoff_save + self.window
        self._aoff_input = self._aoff_status + self.window * p
        self._apacked_len = self._aoff_input + self.window * p * i
        # depth-adaptive dispatch instruments (updated behind enabled
        # checks at the routing sites, the Tracer.span idiom)
        self._m_depth, self._m_waste = depth_dispatch_instruments()

    # ------------------------------------------------------------------

    def _tick_packed_impl(self, ring, state, packed, verify):
        """Unpack the single control-word array (see tick()) and run the
        fused tick. One argument means ONE host->device transfer per tick —
        every transferred buffer pays a latency floor regardless of
        size, so 7 small args cost ~7 floors."""
        W, P, I = self.window, self.num_players, self.game.input_size
        do_load = packed[0] != 0
        load_slot = packed[1]
        advance_count = packed[2]
        start_frame = packed[3]
        save_slots = packed[self._off_save : self._off_status]
        statuses = packed[self._off_status : self._off_input].reshape(W, P)
        inputs = (
            packed[self._off_input : self._packed_len]
            .astype(jnp.uint8)
            .reshape(W, P, I)
        )
        return self._tick_impl(
            ring, state, do_load, load_slot, inputs, statuses, save_slots,
            advance_count, start_frame, verify,
        )

    def _tick_windowed_impl(self, ring, state, packed, verify, nslots):
        """The packed cond tick truncated to its first `nslots` window
        slots (a STATIC value): the scan body, inputs and save slots past
        `nslots` are never traced, so the compiled program's device work
        is proportional to the depth bucket, not the full window.
        Checksums zero-pad back to [W] so batch indexing (flat j*W + i)
        never changes. Bit-identical to _tick_packed_impl whenever every
        dispatched row's last active slot (advance count and highest real
        save) fits in `nslots` — the routers guarantee it, and slots past
        the last active one are provably inert in the full program
        (cond-skipped saves, cond-skipped steps, (0, 0) checksums)."""
        W, P, I = self.window, self.num_players, self.game.input_size
        do_load = packed[0] != 0
        load_slot = packed[1]
        advance_count = packed[2]
        start_frame = packed[3]
        save_slots = packed[self._off_save : self._off_save + nslots]
        statuses = packed[self._off_status : self._off_status + nslots * P]
        statuses = statuses.reshape(nslots, P)
        inputs = (
            packed[self._off_input : self._off_input + nslots * P * I]
            .astype(jnp.uint8)
            .reshape(nslots, P, I)
        )
        ring, state, verify, his, los = self._tick_impl(
            ring, state, do_load, load_slot, inputs, statuses, save_slots,
            advance_count, start_frame, verify, nslots=nslots,
        )
        pad = jnp.zeros((W - nslots,), dtype=his.dtype)
        return (
            ring,
            state,
            verify,
            jnp.concatenate([his, pad]),
            jnp.concatenate([los, pad]),
        )

    def _tick_branchless_impl(self, ring, state, packed, verify, nslots):
        """The T=1 tick with NO device control flow: `nslots` window slots
        are unrolled, every unrolled slot's checksum and step always
        execute, and masking is jnp.where selects. Same packed layout and
        bit-identical outputs to _tick_packed_impl (tests drive random
        streams through both): skipped saves emit (0, 0) checksums and
        write the OLD value back to ring slot 0; skipped steps' results
        are where()-discarded; slots past `nslots` (a STATIC jit key) are
        provably inert for the row being dispatched — the host router
        picks the smallest coalesced variant covering the row's last
        active slot (depth specialization: unrolling the full window cost
        ~1 ms of masked step+checksum work per rollback tick at 65k that
        a depth-5 rollback never needed). Rationale and the measured
        dispatch numbers: the _tick_fn comment in __init__."""
        W, P, I = self.window, self.num_players, self.game.input_size
        do_load = packed[0] != 0
        load_slot = packed[1]
        advance_count = packed[2]
        start_frame = packed[3]
        save_slots = packed[self._off_save : self._off_status]
        statuses = packed[self._off_status : self._off_input].reshape(W, P)
        inputs = (
            packed[self._off_input : self._packed_len]
            .astype(jnp.uint8)
            .reshape(W, P, I)
        )
        loaded = jax.tree.map(
            lambda r: jax.lax.dynamic_index_in_dim(
                r, load_slot, 0, keepdims=False
            ),
            ring,
        )
        state = _tree_where(do_load, loaded, state)
        his, los = [], []
        for i in range(nslots):
            save_slot = save_slots[i]
            do_save = save_slot < self.ring_len
            hi, lo = self.game.checksum(state)
            hi = jnp.where(do_save, hi, jnp.uint32(0))
            lo = jnp.where(do_save, lo, jnp.uint32(0))
            wslot = jnp.where(do_save, save_slot, 0)
            old = jax.tree.map(
                lambda r: jax.lax.dynamic_index_in_dim(
                    r, wslot, 0, keepdims=False
                ),
                ring,
            )
            ring = jax.tree.map(
                lambda r, s: jax.lax.dynamic_update_index_in_dim(
                    r, s, wslot, 0
                ),
                ring,
                _tree_where(do_save, state, old),
            )
            if self.device_verify:
                upd = self._verify_update(verify, start_frame + i, hi, lo)
                verify = _tree_where(do_save, upd, verify)
            nxt = self.game.step(state, inputs[i], statuses[i])
            state = _tree_where(i < advance_count, nxt, state)
            his.append(hi)
            los.append(lo)
        zero = [jnp.uint32(0)] * (W - nslots)
        return (
            ring,
            state,
            verify,
            jnp.stack(his + zero),
            jnp.stack(los + zero),
        )

    def branchless_variants(self):
        """The coalesced slot counts the branchless T=1 program compiles
        for (3, 6, 9, ..., W; always ends in W): a handful of variants
        covers every depth while warmup stays a few compiles, and the
        router rounds a row's last active slot UP to the next variant."""
        if not hasattr(self, "_bl_variants"):
            W = self.window
            self._bl_variants = sorted(
                {min(3 * k, W) for k in range(1, (W + 2) // 3 + 1)}
            )
        return self._bl_variants

    def _tick_multi_impl(self, ring, state, packed, verify, nslots):
        """T buffered ticks as ONE device program: a lax.scan of the packed
        tick over rows of packed[T, L]. Each dispatch costs a fixed
        amount of host time regardless of content, so batching T interactive
        ticks into one dispatch divides the request path's dominant cost
        by T (ggrs_tpu/tpu/backend.py lazy_ticks). Padding rows
        (advance_count=0, scratch-only saves) are true no-ops — the
        per-slot conds skip all work — so one buffer length compiles
        once per depth variant. `nslots` (STATIC) truncates every row's
        scan body to the depth bucket covering the buffer's deepest row:
        a buffer of zero-rollback ticks no longer pays the full window's
        scanned slots per row (cond skips the work inside a slot, but
        each traced slot still costs control flow and — under vmap's
        cond->select lowering in the megabatch — real compute)."""

        def body(carry, row):
            ring, state, verify = carry
            ring, state, verify, his, los = self._tick_windowed_impl(
                ring, state, row, verify, nslots
            )
            return (ring, state, verify), (his, los)

        (ring, state, verify), (his, los) = jax.lax.scan(
            body, (ring, state, verify), packed
        )
        return ring, state, verify, his, los

    def _tick_fast_impl(self, ring, state, row):
        """The per-slot ZERO-ROLLBACK fast tick: the single-session body
        the resident virtual-tick driver vmaps in-loop
        (MultiSessionDeviceCore._driver_fast_impl) when every row of a
        mailbox fill cycle is fast-eligible — no load, at most one
        advance, no active slot past window slot 1. The math is the
        megabatch fast program's (_dispatch_fast_impl) per slot: no ring
        gather/scatter beyond the two masked single-slot writes, no
        resim scan — one step, two checksums. Masked saves write the
        slot's OLD ring value back (the branchless trick), so even the
        ring's bytes stay bit-identical to the cond program; pad rows
        (advance 0, scratch saves) are inert. Checksums land at window
        slots 0/1 of a zero [W] batch, keeping the flat indexing."""
        W, P, I = self.window, self.num_players, self.game.input_size
        advance = row[2]
        s0 = row[self._off_save]
        s1 = row[self._off_save + 1]
        statuses0 = row[self._off_status : self._off_status + P]
        inputs0 = (
            row[self._off_input : self._off_input + P * I]
            .astype(jnp.uint8)
            .reshape(P, I)
        )
        zero = jnp.uint32(0)

        def ring_write(ring, do, wslot, value):
            old = jax.tree.map(
                lambda r: jax.lax.dynamic_index_in_dim(
                    r, wslot, 0, keepdims=False
                ),
                ring,
            )
            return jax.tree.map(
                lambda r, s: jax.lax.dynamic_update_index_in_dim(
                    r, s, wslot, 0
                ),
                ring,
                _tree_where(do, value, old),
            )

        # slot 0: masked save of the pre-step state
        hi0, lo0 = self.game.checksum(state)
        do0 = s0 < self.ring_len
        ring = ring_write(ring, do0, jnp.where(do0, s0, 0), state)
        # the one advance (masked only so pad rows stay inert)
        nxt = self.game.step(state, inputs0, statuses0)
        state = _tree_where(advance > 0, nxt, state)
        # slot 1: masked trailing save of the post-step state
        hi1, lo1 = self.game.checksum(state)
        do1 = s1 < self.ring_len
        ring = ring_write(ring, do1, jnp.where(do1, s1, 0), state)
        his = jnp.zeros((W,), dtype=hi0.dtype)
        los = jnp.zeros((W,), dtype=lo0.dtype)
        his = his.at[0].set(jnp.where(do0, hi0, zero))
        his = his.at[1].set(jnp.where(do1, hi1, zero))
        los = los.at[0].set(jnp.where(do0, lo0, zero))
        los = los.at[1].set(jnp.where(do1, lo1, zero))
        return ring, state, his, los

    def _branchless_nslots(
        self, row: np.ndarray, last_active: Optional[int] = None
    ) -> int:
        """Smallest coalesced variant covering the row's last active slot
        (its advance count and its highest real save). `last_active` is the
        caller's precomputed 1-based last active slot (the backend's parse
        already knows it), skipping the save-slot rescan."""
        if last_active is None:
            save_slots = np.asarray(row[self._off_save : self._off_status])
            last_active = max(int(row[2]), 1)
            valid = np.nonzero(save_slots < self.ring_len)[0]
            if valid.size:
                last_active = max(last_active, int(valid[-1]) + 1)
        return self.variant_for(last_active)

    def variant_for(self, last_active: int) -> int:
        """Smallest coalesced depth variant covering a 1-based last
        active slot — THE rounding rule every depth-routed path shares
        (T=1 branchless, the lazy multi-tick scan)."""
        for v in self.branchless_variants():
            if v >= last_active:
                return v
        raise ContractViolation(
            f"no variant covers {last_active} slots (variants end in window)"
        )

    def _pallas_t1(self) -> bool:
        """Do lone ticks route through the pallas tick kernel? Size-aware
        (see PALLAS_T1_MIN_ENTITIES): on big worlds the kernel's
        size-flat VMEM streaming beats every XLA T=1 program."""
        n = getattr(self.game, "num_entities", None)
        return (
            self._tick_pallas_fn is not None
            and n is not None
            and n >= self.PALLAS_T1_MIN_ENTITIES
        )

    def tick_row(
        self, row: np.ndarray, last_active: Optional[int] = None
    ) -> Tuple[Any, Any]:
        """One packed tick row through the (warmup-compiled) single-tick
        program; returns (checksum_hi[W], checksum_lo[W]). `last_active`
        (optional) is the row's 1-based last active slot, precomputed by
        the backend's parse so variant routing skips a save-slot rescan."""
        if self._pallas_t1():
            self.ring, self.state, self.verify, his, los = (
                self._tick_pallas_fn(
                    self.ring, self.state, row[None, :], self.verify
                )
            )
            return his[0], los[0]
        # row-content routing (rationale: the __init__ comment): rollback
        # / multi-advance rows run the branchless program at the smallest
        # depth variant covering the row; trivial rows keep cond
        if self._tick_branchless_fn is not None and (
            row[0] != 0 or row[2] > 1
        ):
            nslots = self._branchless_nslots(row, last_active)
            if GLOBAL_TELEMETRY.enabled:
                self._m_depth.observe(nslots)
                self._m_waste.inc(self.window - nslots)
            self.ring, self.state, self.verify, his, los = (
                self._tick_branchless_fn(
                    self.ring, self.state, row, self.verify, nslots,
                )
            )
            return his, los
        # trivial rows (mid-size worlds): the windowed cond program at
        # the smallest covering variant — same cond skipping, a fraction
        # of the scanned slots. Worlds below T1_WINDOWED_MIN_ENTITIES
        # keep the full cond program (the saved slots are not worth a
        # per-core compile there), and worlds past the branchless cap
        # keep it untouched too (their routing economics were measured
        # there; a rollback row's variant can reach W anyway).
        if self._t1_windowed:
            nslots = self._branchless_nslots(row, last_active)
            if nslots < self.window:
                if GLOBAL_TELEMETRY.enabled:
                    self._m_depth.observe(nslots)
                    self._m_waste.inc(self.window - nslots)
                self.ring, self.state, self.verify, his, los = (
                    self._tick_windowed_fn(
                        self.ring, self.state, row, self.verify, nslots,
                    )
                )
                return his, los
        self.ring, self.state, self.verify, his, los = self._tick_fn(
            self.ring, self.state, row, self.verify
        )
        return his, los

    def tick_multi(
        self, rows: np.ndarray, last_active: Optional[int] = None
    ) -> Tuple[Any, Any]:
        """Run T packed ticks (layout: see tick()) in one dispatch; returns
        (checksum_hi[T, W], checksum_lo[T, W]) as device arrays. Multi-row
        dispatches route to the pallas tick kernel when the core has one:
        streaming state + ring through VMEM amortizes over the rows, and
        the kernel wins from T=2 up (measured 2.3x at T=4, 3-4x at T=16 on
        a 65k world). T=1 stays on the XLA scan on small/mid worlds,
        whose lax.cond slot skipping beats the kernel's masked full
        window for a lone tick — but routes to the kernel from
        PALLAS_T1_MIN_ENTITIES up, where every XLA T=1 program's unfused
        passes cost more than the kernel's size-flat streaming.

        `last_active` (optional): the MAX 1-based last active slot across
        the buffered rows, precomputed by the backend's parse. The XLA
        scan then runs the depth variant covering it instead of the full
        window — bit-identical (slots past every row's last active one
        are inert) at a fraction of the scanned device work. None keeps
        the full-window program (the depth-routing-off reference). The
        pallas kernel path ignores it: the kernel's VMEM streaming is
        already window-flat."""
        if self._tick_pallas_fn is not None and (
            rows.shape[0] > 1 or self._pallas_t1()
        ):
            self.ring, self.state, self.verify, his, los = (
                self._tick_pallas_fn(self.ring, self.state, rows, self.verify)
            )
            return his, los
        nslots = (
            self.window if last_active is None else self.variant_for(last_active)
        )
        if GLOBAL_TELEMETRY.enabled and last_active is not None:
            self._m_depth.observe(nslots)
            self._m_waste.inc((self.window - nslots) * int(rows.shape[0]))
        self.ring, self.state, self.verify, his, los = self._tick_multi_fn(
            self.ring, self.state, rows, self.verify, nslots
        )
        return his, los

    def _verify_update(self, verify, frame, hi, lo):
        """First-seen history record/compare + mismatch latch (the device
        twin of the fused session's _save_and_check). Static no-op when
        device verification is off."""
        if not self.device_verify:
            return verify
        h = frame % self.ring_len
        seen = verify["h_tag"][h] == frame
        differs = seen & (
            (verify["h_hi"][h] != hi) | (verify["h_lo"][h] != lo)
        )
        first = differs & (verify["flag"][0] == 0)
        flag = verify["flag"]
        flag = flag.at[0].set(jnp.where(differs, 1, flag[0]))
        flag = flag.at[1].set(jnp.where(first, frame, flag[1]))
        return {
            "h_tag": verify["h_tag"].at[h].set(frame),
            "h_hi": verify["h_hi"].at[h].set(
                jnp.where(seen, verify["h_hi"][h], hi)
            ),
            "h_lo": verify["h_lo"].at[h].set(
                jnp.where(seen, verify["h_lo"][h], lo)
            ),
            "flag": flag,
        }

    def _tick_impl(
        self,
        ring,
        state,
        do_load,  # bool[]
        load_slot,  # i32[]
        inputs,  # u8[W, P, input_size]
        statuses,  # i32[W, P]
        save_slots,  # i32[S]; scratch_slot means "no save"
        advance_count,  # i32[]
        start_frame,  # i32[]; frame of the first window slot
        verify,  # device-verify carry ({} when disabled)
        nslots=None,  # static slot count (None = the full window)
    ):
        if nslots is None:
            nslots = self.window
        loaded = jax.tree.map(
            lambda r: jax.lax.dynamic_index_in_dim(r, load_slot, 0, keepdims=False),
            ring,
        )
        state = _tree_where(do_load, loaded, state)

        iota = jnp.arange(nslots, dtype=jnp.int32)

        def body(carry, xs):
            ring, state, verify = carry
            i, inp, stat, save_slot = xs
            # save-then-advance: slot i snapshots the pre-advance state.
            # lax.cond (not a masked select) so skipped slots cost nothing:
            # XLA executes only the taken branch, making the tick's device
            # time proportional to the ACTUAL rollback depth and save
            # count, not to the static window (a no-rollback tick runs one
            # step + one checksum instead of W of each).
            do_save = save_slot < self.ring_len

            def save(args):
                ring, state, verify = args
                hi, lo = self.game.checksum(state)
                ring = jax.tree.map(
                    lambda r, s: jax.lax.dynamic_update_index_in_dim(
                        r, s, save_slot, 0
                    ),
                    ring,
                    state,
                )
                verify = self._verify_update(verify, start_frame + i, hi, lo)
                return ring, verify, hi, lo

            def skip(args):
                ring, _, verify = args
                return ring, verify, jnp.uint32(0), jnp.uint32(0)

            ring, verify, hi, lo = jax.lax.cond(
                do_save, save, skip, (ring, state, verify)
            )
            state = jax.lax.cond(
                i < advance_count,
                lambda s: self.game.step(s, inp, stat),
                lambda s: s,
                state,
            )
            return (ring, state, verify), (hi, lo)

        (ring, state, verify), (his, los) = jax.lax.scan(
            body, (ring, state, verify), (iota, inputs, statuses, save_slots)
        )
        return ring, state, verify, his, los

    # ------------------------------------------------------------------

    def pack_tick_row(
        self,
        do_load: bool,
        load_slot: int,
        inputs: np.ndarray,
        statuses: np.ndarray,
        save_slots: np.ndarray,
        advance_count: int,
        start_frame: int = 0,
    ) -> np.ndarray:
        """Build one tick's packed control-word row (the _tick_packed_impl
        layout) — dispatched alone by tick() or buffered for a multi-tick
        dispatch by the backend's lazy batching."""
        packed = np.empty((self._packed_len,), dtype=np.int32)
        self.pack_tick_row_into(
            packed, do_load, load_slot, inputs, statuses, save_slots,
            advance_count, start_frame,
        )
        return packed

    def pack_tick_row_into(
        self,
        out: np.ndarray,
        do_load: bool,
        load_slot: int,
        inputs: np.ndarray,
        statuses: np.ndarray,
        save_slots: np.ndarray,
        advance_count: int,
        start_frame: int = 0,
    ) -> np.ndarray:
        """pack_tick_row writing into a caller-owned buffer. The async
        dispatch pipeline stages rows in a small rotating pool instead of
        allocating per tick; the buffer handed to a dispatch must not be
        reused until that dispatch's slot rotates back around (the backend's
        double-buffering guarantees it)."""
        out[0] = 1 if do_load else 0
        out[1] = load_slot
        out[2] = advance_count
        out[3] = start_frame
        out[self._off_save : self._off_status] = save_slots
        out[self._off_status : self._off_input] = statuses.reshape(-1)
        out[self._off_input :] = inputs.reshape(-1)
        return out

    def pad_tick_row(self) -> np.ndarray:
        """A true no-op tick row (no load, zero advances, scratch-only
        saves): pads a partial lazy buffer so one buffer length compiles
        once."""
        return self.pack_tick_row(
            False,
            0,
            np.zeros((self.window, self.num_players, self.game.input_size),
                     dtype=np.uint8),
            np.zeros((self.window, self.num_players), dtype=np.int32),
            np.full((self.window,), self.scratch_slot, dtype=np.int32),
            0,
        )

    def tick(
        self,
        do_load: bool,
        load_slot: int,
        inputs: np.ndarray,
        statuses: np.ndarray,
        save_slots: np.ndarray,
        advance_count: int,
        start_frame: int = 0,
    ) -> Tuple[Any, Any]:
        """Run one fused tick; returns (checksum_hi[W], checksum_lo[W]) as
        device arrays (no host sync). `start_frame` feeds the device-verify
        history (slot i saves frame start_frame + i)."""
        packed = self.pack_tick_row(
            do_load, load_slot, inputs, statuses, save_slots, advance_count,
            start_frame,
        )
        return self.tick_row(packed)

    def check_device_verdict(self) -> Tuple[bool, int]:
        """Fetch the device-verify latch: (mismatch?, first bad frame).
        ONE small host readback — the only transfer device verification
        ever makes."""
        assert self.device_verify, "core built without device_verify"
        flag = np.asarray(jax.device_get(self.verify["flag"]))
        return bool(flag[0]), int(flag[1])

    # ------------------------------------------------------------------
    # speculative beam (the north-star "rollback becomes a select"):
    # evaluate B candidate input futures from a ring snapshot ahead of
    # input confirmation; a later rollback whose corrected script matches a
    # member adopts its precomputed trajectory instead of resimulating.
    # ------------------------------------------------------------------

    def _speculate_impl(self, ring, anchor_slot, beam_inputs, beam_statuses):
        """beam_inputs u8[B, W, P, I], beam_statuses i32[B, W, P] ->
        per-member per-frame trajectories [B, W, ...], per-frame checksums
        [B, W] (of the state AFTER each step), and the anchor's checksum."""
        if (
            self._beam_sharding is not None
            and beam_inputs.shape[0] % self.mesh.shape["beam"] == 0
        ):
            beam_inputs = jax.lax.with_sharding_constraint(
                beam_inputs, self._beam_sharding
            )
            beam_statuses = jax.lax.with_sharding_constraint(
                beam_statuses, self._beam_sharding
            )
        anchor = jax.tree.map(
            lambda r: jax.lax.dynamic_index_in_dim(r, anchor_slot, 0, keepdims=False),
            ring,
        )
        a_hi, a_lo = self.game.checksum(anchor)

        def rollout_one(inputs, statuses):
            def body(s, xs):
                inp, stat = xs
                nxt = self.game.step(s, inp, stat)
                hi, lo = self.game.checksum(nxt)
                return nxt, (nxt, hi, lo)

            _, (traj, his, los) = jax.lax.scan(body, anchor, (inputs, statuses))
            return traj, his, los

        traj, his, los = jax.vmap(rollout_one)(beam_inputs, beam_statuses)
        return traj, his, los, a_hi, a_lo

    def _speculate_pallas(self, anchor_slot, beam_inputs):
        """Pallas-rollout speculation: gather the anchor snapshot, then run
        the entity-tiled beam kernel on it. Output tuple matches
        _speculate_impl bit-for-bit (all-CONFIRMED statuses). A rollout the
        kernel rejects (reduce-phase adapter whose B*L trajectory windows
        exceed the single-tile budget) demotes this core to the XLA
        speculation path permanently — same results, unfused cost."""
        B = int(beam_inputs.shape[0])
        if B not in self._beam_rollouts:
            from .pallas_beam import PallasBeamRollout, ShardedPallasBeamRollout

            try:
                if self.mesh is not None:
                    self._beam_rollouts[B] = ShardedPallasBeamRollout(
                        self.game,
                        self.num_players,
                        B,
                        self.mesh,
                        interpret=self.spec_backend.endswith("-interpret"),
                        max_rollout=self.window,
                    )
                else:
                    self._beam_rollouts[B] = PallasBeamRollout(
                        self.game,
                        self.num_players,
                        B,
                        interpret=self.spec_backend.endswith("-interpret"),
                        max_rollout=self.window,  # VMEM budget sized to worst case
                    )
            except (AssertionError, ValueError) as e:
                # narrow on purpose (r3 advisor): a broken adapter should
                # surface, only a sizing rejection falls back
                import warnings

                warnings.warn(
                    f"pallas beam rollout unavailable for "
                    f"{type(self.game).__name__} (B={B}): {e}; speculating "
                    "via the XLA path"
                )
                self.spec_backend = "xla"
                return self._speculate_fn(
                    self.ring,
                    np.int32(anchor_slot),
                    beam_inputs,
                    np.zeros(beam_inputs.shape[:3], dtype=np.int32),
                )
            rollout = self._beam_rollouts[B]

            def impl(ring, anchor_slot, beam_inputs):
                anchor = jax.tree.map(
                    lambda r: jax.lax.dynamic_index_in_dim(
                        r, anchor_slot, 0, keepdims=False
                    ),
                    ring,
                )
                a_hi, a_lo = self.game.checksum(anchor)
                traj, his, los = rollout.rollout(anchor, beam_inputs)
                return traj, his, los, a_hi, a_lo

            self._speculate_pallas_fns[B] = jax.jit(impl)
        return self._speculate_pallas_fns[B](
            self.ring, np.int32(anchor_slot), beam_inputs
        )

    def speculate(self, anchor_slot: int, beam_inputs: np.ndarray,
                  beam_statuses: np.ndarray):
        """Dispatch a beam rollout from ring slot `anchor_slot` (async).
        The pallas backend speculates under the all-CONFIRMED statuses
        contract (the only way the beam is ever used); rollouts with any
        non-CONFIRMED status fall back to the XLA path."""
        if self.spec_backend.startswith("pallas") and not np.any(
            np.asarray(beam_statuses)
        ):
            return self._speculate_pallas(anchor_slot, beam_inputs)
        return self._speculate_fn(
            self.ring, np.int32(anchor_slot), beam_inputs, beam_statuses
        )

    def _adopt_impl(self, ring, traj, spec_his, spec_los, a_hi, a_lo, verify,
                    packed):
        """Commit a beam member's trajectory as (the prefix of) this tick's
        result. The first `matched` frames are served from the speculation:
        ring slots fill with the member's precomputed per-frame states
        (slot i = state at load_frame + i = trajectory index shift+i-1) and
        their checksums come from the speculation — no step or checksum
        math reruns. Frames past `matched` RESIMULATE from the member's
        frame load+matched state with the actual corrected inputs, exactly
        like _tick_impl, in this same dispatch — one wrong byte from one
        player no longer discards an otherwise-correct trajectory, it
        costs only the mispredicted suffix (the TPU analog of the
        reference's per-player misprediction localization,
        src/input_queue.rs:167-204). `matched == advance_count` is the
        full adoption. `shift` offsets into the trajectory: the
        speculation was anchored `shift` frames BEFORE the rollback's load
        frame — depth jitter doesn't invalidate the speculation. Control
        words + suffix inputs ride one packed array for the same
        one-transfer reason as _tick_packed_impl."""
        W, P, I = self.window, self.num_players, self.game.input_size
        member = packed[0]
        load_slot = packed[1]
        advance_count = packed[2]
        shift = packed[3]
        load_frame = packed[4]
        matched = packed[5]
        save_slots = packed[self._aoff_save : self._aoff_status]
        statuses = packed[self._aoff_status : self._aoff_input].reshape(W, P)
        inputs = (
            packed[self._aoff_input : self._apacked_len]
            .astype(jnp.uint8)
            .reshape(W, P, I)
        )
        loaded = jax.tree.map(
            lambda r: jax.lax.dynamic_index_in_dim(r, load_slot, 0, keepdims=False),
            ring,
        )
        mtraj = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, member, 0, keepdims=False),
            traj,
        )
        mhis = jax.lax.dynamic_index_in_dim(spec_his, member, 0, keepdims=False)
        mlos = jax.lax.dynamic_index_in_dim(spec_los, member, 0, keepdims=False)
        # checksums of frames anchor..anchor+rollout, windowed at shift;
        # zero-pad so dynamic_slice never clamps (entries past shift+matched
        # are never read: suffix saves compute their checksums fresh)
        pad = jnp.zeros((self.window - 1,), dtype=a_hi.dtype)
        full_hi = jnp.concatenate([a_hi[None], mhis, pad])
        full_lo = jnp.concatenate([a_lo[None], mlos, pad])
        his = jax.lax.dynamic_slice(full_hi, (shift,), (self.window,))
        los = jax.lax.dynamic_slice(full_lo, (shift,), (self.window,))

        iota = jnp.arange(self.window, dtype=jnp.int32)

        def body(carry, xs):
            ring, state, verify = carry
            i, inp, stat, save_slot, spec_hi, spec_lo = xs
            # slots i <= matched enter on the precomputed trajectory state
            # of frame load+i (idx < 0 only at shift=0, i=0: the anchor
            # snapshot itself); later slots carry the resimulated state.
            # The gather is cond-gated and fires ONLY where the trajectory
            # state is actually consumed — a saved prefix slot, or the
            # i == matched slot that seeds the resimulated suffix. Prefix
            # slots that save nothing, suffix slots and scratch padding pay
            # nothing (an ungated per-slot gather measurably made partial
            # adoption cost more device time than the resim it replaced).
            idx = shift + i - 1

            def from_traj(state):
                prev = jax.tree.map(
                    lambda t: jax.lax.dynamic_index_in_dim(
                        t, jnp.maximum(idx, 0), 0, keepdims=False
                    ),
                    mtraj,
                )
                return _tree_where(idx < 0, loaded, prev)

            need_traj = (i <= matched) & (
                (save_slot < self.ring_len) | (i == matched)
            )
            state = jax.lax.cond(need_traj, from_traj, lambda s: s, state)
            use_spec = i <= matched

            def save(args):
                ring, state, verify = args
                hi, lo = jax.lax.cond(
                    use_spec,
                    lambda s: (spec_hi, spec_lo),
                    lambda s: self.game.checksum(s),
                    state,
                )
                ring = jax.tree.map(
                    lambda r, s: jax.lax.dynamic_update_index_in_dim(
                        r, s, save_slot, 0
                    ),
                    ring,
                    state,
                )
                verify = self._verify_update(verify, load_frame + i, hi, lo)
                return ring, verify, hi, lo

            def skip(args):
                ring, _, verify = args
                return ring, verify, jnp.uint32(0), jnp.uint32(0)

            # scratch-slot writes skipped outright (same cond rationale as
            # _tick_impl: device time tracks the actual save count)
            ring, verify, hi, lo = jax.lax.cond(
                save_slot < self.ring_len, save, skip, (ring, state, verify)
            )
            # only the mispredicted suffix resimulates; served frames'
            # states come from the trajectory selects above
            state = jax.lax.cond(
                (i >= matched) & (i < advance_count),
                lambda s: self.game.step(s, inp, stat),
                lambda s: s,
                state,
            )
            return (ring, state, verify), (hi, lo)

        (ring, state, verify), (out_his, out_los) = jax.lax.scan(
            body, (ring, loaded, verify),
            (iota, inputs, statuses, save_slots, his, los),
        )
        return ring, state, verify, out_his, out_los

    def _adopt_full_impl(self, ring, traj, spec_his, spec_los, a_hi, a_lo,
                         verify, packed):
        """Branchless FULL-hit adoption: bit-identical to _adopt_impl when
        matched == advance_count (adopt() routes only that case here).
        Every slot's state is a select over the member trajectory, every
        saved checksum comes from the speculation, masked saves write the
        OLD value back to slot 0 — no scan, no cond, no game math. The
        packed layout is _adopt_impl's; the suffix input/status words ride
        along unused so both programs share one host-side pack."""
        member = packed[0]
        load_slot = packed[1]
        shift = packed[3]
        load_frame = packed[4]
        matched = packed[5]
        save_slots = packed[self._aoff_save : self._aoff_status]
        loaded = jax.tree.map(
            lambda r: jax.lax.dynamic_index_in_dim(
                r, load_slot, 0, keepdims=False
            ),
            ring,
        )
        mtraj = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, member, 0, keepdims=False),
            traj,
        )
        mhis = jax.lax.dynamic_index_in_dim(spec_his, member, 0, keepdims=False)
        mlos = jax.lax.dynamic_index_in_dim(spec_los, member, 0, keepdims=False)
        pad = jnp.zeros((self.window - 1,), dtype=a_hi.dtype)
        full_hi = jnp.concatenate([a_hi[None], mhis, pad])
        full_lo = jnp.concatenate([a_lo[None], mlos, pad])
        his_w = jax.lax.dynamic_slice(full_hi, (shift,), (self.window,))
        los_w = jax.lax.dynamic_slice(full_lo, (shift,), (self.window,))

        his, los = [], []
        state = loaded
        for i in range(self.window):
            # with no suffix to resimulate, the state entering slot i is
            # trajectory index shift + min(i, matched) - 1 (the anchor
            # snapshot itself when that is negative: shift == 0, i == 0)
            eff = shift + jnp.minimum(i, matched) - 1
            prev = jax.tree.map(
                lambda t: jax.lax.dynamic_index_in_dim(
                    t, jnp.maximum(eff, 0), 0, keepdims=False
                ),
                mtraj,
            )
            state = _tree_where(eff < 0, loaded, prev)
            save_slot = save_slots[i]
            do_save = save_slot < self.ring_len
            hi = jnp.where(do_save, his_w[i], jnp.uint32(0))
            lo = jnp.where(do_save, los_w[i], jnp.uint32(0))
            wslot = jnp.where(do_save, save_slot, 0)
            old = jax.tree.map(
                lambda r: jax.lax.dynamic_index_in_dim(
                    r, wslot, 0, keepdims=False
                ),
                ring,
            )
            ring = jax.tree.map(
                lambda r, s: jax.lax.dynamic_update_index_in_dim(
                    r, s, wslot, 0
                ),
                ring,
                _tree_where(do_save, state, old),
            )
            if self.device_verify:
                upd = self._verify_update(verify, load_frame + i, hi, lo)
                verify = _tree_where(do_save, upd, verify)
            his.append(hi)
            los.append(lo)
        return ring, state, verify, jnp.stack(his), jnp.stack(los)

    def pack_adopt_row(self, member: int, load_slot: int,
                       advance_count: int, shift: int, load_frame: int,
                       matched: int, save_slots: np.ndarray,
                       statuses: Optional[np.ndarray] = None,
                       inputs: Optional[np.ndarray] = None) -> np.ndarray:
        """Build one adoption's packed control-word row (the _adopt_impl
        layout) — THE one definition of the adopt layout, shared by
        adopt() and the serving megabatch's per-slot adoption
        (MultiSessionDeviceCore.adopt_slot)."""
        packed = np.zeros((self._apacked_len,), dtype=np.int32)
        packed[0] = member
        packed[1] = load_slot
        packed[2] = advance_count
        packed[3] = shift
        packed[4] = load_frame
        packed[5] = matched
        packed[self._aoff_save : self._aoff_status] = save_slots
        if statuses is not None:
            packed[self._aoff_status : self._aoff_input] = statuses.reshape(-1)
        if inputs is not None:
            packed[self._aoff_input :] = inputs.reshape(-1)
        return packed

    def adopt(self, spec, member: int, load_slot: int, save_slots: np.ndarray,
              advance_count: int, shift: int = 0, load_frame: int = 0,
              inputs: Optional[np.ndarray] = None,
              statuses: Optional[np.ndarray] = None,
              matched: Optional[int] = None) -> Tuple[Any, Any]:
        """Fulfill a rollback tick from a (prefix-)matching speculation;
        returns (checksum_hi[W], checksum_lo[W]) like tick(). `shift` =
        load_frame - anchor_frame (caller guarantees the member's first
        `shift` input rows equal the inputs actually played for frames
        anchor..load). `matched` (default: advance_count, the full
        adoption) is how many corrected frames the member's rows match;
        the rest resimulate from `inputs`/`statuses` in this dispatch —
        required whenever matched < advance_count."""
        if matched is None:
            matched = advance_count
        assert matched == advance_count or inputs is not None, (
            "partial adoption needs the corrected inputs for the suffix"
        )
        traj, spec_his, spec_los, a_hi, a_lo = spec
        packed = self.pack_adopt_row(
            member, load_slot, advance_count, shift, load_frame, matched,
            save_slots, statuses=statuses, inputs=inputs,
        )
        # full hits route to the branchless pure-data-movement program
        # (see the _adopt_full_fn comment in __init__); partial hits keep
        # the cond program for its genuine suffix resimulation
        fn = (
            self._adopt_full_fn
            if matched == advance_count and self._adopt_full_fn is not None
            else self._adopt_fn
        )
        if fn is self._adopt_full_fn:
            # contract guard: _adopt_full_impl sources EVERY saved slot's
            # checksum from the speculation window (his_w[i]), while
            # _adopt_impl computes fresh checksums for slots past
            # `matched`. The two are bit-identical only because no caller
            # requests a real save past advance_count on a full hit — a
            # caller violating that would get speculation checksums for
            # frames the speculation never covered, silently.
            assert (
                save_slots[advance_count + 1 :] >= self.ring_len
            ).all(), (
                "full-hit adoption requires every save slot past "
                "advance_count to be scratch (speculation checksums do "
                "not cover frames beyond the adopted window)"
            )
        self.ring, self.state, self.verify, his, los = fn(
            self.ring, traj, spec_his, spec_los, a_hi, a_lo, self.verify,
            packed,
        )
        return his, los

    def reset(self) -> None:
        """Return the core to its initial condition (fresh world, zeroed
        ring and verify carry) WITHOUT recompiling anything — a new
        session can reuse a warmed core's compiled programs (each compile
        costs seconds)."""
        state = self.game.init_state()
        if self.mesh is not None:
            from ..parallel.sharded import shard_state

            state = shard_state(state, self.mesh)
        self.state = state
        self.ring = jax.tree.map(jnp.zeros_like, self.ring)
        if self.device_verify:
            self.verify = {
                "h_tag": jnp.full_like(self.verify["h_tag"], -1),
                "h_hi": jnp.zeros_like(self.verify["h_hi"]),
                "h_lo": jnp.zeros_like(self.verify["h_lo"]),
                # device_put onto the existing sharding: a bare asarray
                # would drop the mesh placement __init__ applied and make
                # the next donated tick recompile (or reject the pytree)
                "flag": jax.device_put(
                    np.array([0, -1], dtype=np.int32),
                    self.verify["flag"].sharding,
                ),
            }

    def fetch_state(self):
        """Device -> host copy of the live state (test/debug aid)."""
        return jax.device_get(self.state)

    def fetch_ring_slot(self, slot: int):
        return jax.device_get(
            jax.tree.map(lambda r: r[slot], self.ring)
        )
