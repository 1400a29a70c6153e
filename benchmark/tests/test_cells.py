"""Each cell's correctness check at a test size on the CPU: sound runs come
out correct, the control (the reference at int16 in the program's place)
does not, and neither does a run with the timed path broken underneath, once
for each fault the cell can have:
- a step that returns its state unchanged;
- half of the batch left out (megabatch rows / a SyncTest batch's frames);
- the exchange between chips left out (the psum of the mesh cell);
- an answer altered where it is produced (a checksum);
- a SyncTest rollback shallower than the cell's check_distance, or none.
The SyncTest cells run the pallas kernels that are timed, in interpret mode.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmark.tests import cells

SERVE = "exgame_2p_4096.serve_wan"
DEEP = "exgame_4p_w12.serve_deep"
SYNC = "exgame_2p_4096.synctest_d8"
MESH = "ecs_13056_d16.synctest_mesh4"
MESH_SIZES = {"entities": 512, "backend": "pallas-tiled-interpret"}


def wrong(r):
    return not r["correct"] and any(
        v["value"] > v["limit"] for v in r["compared"].values()
    )


@pytest.mark.parametrize("workload", [SERVE, DEEP, SYNC])
def test_sound_run_is_correct(workload):
    r = cells.run(workload)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    keys = list(r)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(keys)
    assert keys[-1] == "compared"
    assert all(v["limit"] == 0 for v in r["compared"].values())


def test_sound_mesh_run_is_correct():
    r = cells.run(MESH, sizes=MESH_SIZES, seconds=0.5)
    assert r["correct"], r["compared"]


@pytest.mark.parametrize("workload", [SERVE, DEEP, SYNC])
def test_control_is_not_correct(workload):
    assert wrong(cells.run(workload, control=True))


def _step_unchanged(monkeypatch):
    """The model's step (the megabatch's) and its planes adapter's (the
    pallas kernels') return their state unchanged."""
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.tpu.pallas_core import ExGamePlanes

    monkeypatch.setattr(ExGame, "step", lambda self, state, inputs, st: state)
    monkeypatch.setattr(ExGamePlanes, "step",
                        lambda self, pl, inputs, ctx, red=None: pl)


def _checksum_altered(monkeypatch):
    from ggrs_tpu.models.ex_game import ExGame

    orig = ExGame.checksum

    def altered(self, state):
        hi, lo = orig(self, state)
        return hi, lo + jnp.uint32(1)

    monkeypatch.setattr(ExGame, "checksum", altered)

    from ggrs_tpu.tpu.pallas_core import PallasSyncTestCore

    orig_planes = PallasSyncTestCore._checksum_planes

    def altered_planes(self, planes, gi, frame):
        hi, lo = orig_planes(self, planes, gi, frame)
        return hi, lo + 1

    monkeypatch.setattr(PallasSyncTestCore, "_checksum_planes", altered_planes)


def _half_megabatch(monkeypatch):
    """The megabatch programs leave the second half of their rows out:
    those slots keep their worlds and rings as they were."""
    from ggrs_tpu.tpu.backend import MultiSessionDeviceCore as Core

    def halve(orig):
        def impl(self, rings, states, idx, rows, *rest):
            out = orig(self, rings, states, idx, rows, *rest)
            drop = idx[idx.shape[0] - idx.shape[0] // 2:]
            keep = lambda new, old: new.at[drop].set(old[drop])  # noqa: E731
            return (jax.tree.map(keep, out[0], rings),
                    jax.tree.map(keep, out[1], states)) + tuple(out[2:])
        return impl

    monkeypatch.setattr(Core, "_dispatch_impl", halve(Core._dispatch_impl))
    monkeypatch.setattr(Core, "_dispatch_fast_impl",
                        halve(Core._dispatch_fast_impl))


def _half_synctest_batch(monkeypatch):
    """Each batch runs only its first half of frames, the session counting
    all of them."""
    from ggrs_tpu.tpu import TpuSyncTestSession

    orig = TpuSyncTestSession.advance_frames

    def half(self, raw):
        n = raw.shape[0]
        orig(self, raw[: n // 2])
        self.current_frame += n - n // 2

    monkeypatch.setattr(TpuSyncTestSession, "advance_frames", half)


def _rollback_depth(depth):
    def plant(monkeypatch):
        """Every rollback resimulates `depth(d)` frames, not the cell's d
        (a depth of 1 re-saves no frame: no resimulation is compared)."""
        from ggrs_tpu.tpu import TpuSyncTestSession

        orig = TpuSyncTestSession.__init__

        def init(self, game, num_players, check_distance, **kw):
            orig(self, game, num_players, depth(check_distance), **kw)

        monkeypatch.setattr(TpuSyncTestSession, "__init__", init)
    return plant


_half_depth = _rollback_depth(lambda d: d // 2)
_no_resim = _rollback_depth(lambda d: 1)


def _no_exchange(monkeypatch):
    """The sharded kernel's psum over the entity axis left out: each shard
    keeps its partial checksum."""
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)


@pytest.mark.parametrize("workload,fault", [
    (SERVE, _step_unchanged),
    (SERVE, _half_megabatch),
    (SERVE, _checksum_altered),
    (SYNC, _step_unchanged),
    (SYNC, _half_synctest_batch),
    (SYNC, _checksum_altered),
])
def test_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    assert wrong(cells.run(workload))


@pytest.mark.parametrize("fault", [_half_depth, _no_resim])
def test_shallow_rollback_fails_only_the_depth_probe(fault, monkeypatch):
    """Nothing but the probe can see the depth: the resimulated frames feed
    the frontier bit for bit."""
    fault(monkeypatch)
    r = cells.run(SYNC)
    assert {k for k, v in r["compared"].items()
            if v["value"] > v["limit"]} == {"depth_probe_missed"}


def test_mesh_half_depth_is_not_correct(monkeypatch):
    _half_depth(monkeypatch)
    assert wrong(cells.run(MESH, sizes=MESH_SIZES, seconds=0.5))


def test_mesh_without_exchange_is_not_correct(monkeypatch):
    _no_exchange(monkeypatch)
    assert wrong(cells.run(MESH, sizes=MESH_SIZES, seconds=0.5))
