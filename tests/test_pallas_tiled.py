"""Entity-tiled pallas kernel (ggrs_tpu/tpu/pallas_tiled.py): full-carry
bit parity with the XLA scan across multiple tiles and batch boundaries,
divergence detection through the post-pass verdict, that verdict against
a sequential oracle, and the tileability gate. Interpreter mode on the CPU mesh; real-TPU parity at 1M entities is
exercised by bench.py's roofline phase."""

import numpy as np
import pytest

import jax
import jax.tree_util as jtu

from ggrs_tpu.models.ex_game import ExGame
from ggrs_tpu.tpu import TpuSyncTestSession

P = 2


def drive(backend, script, entities, check_distance, batches=3, **kw):
    sess = TpuSyncTestSession(
        ExGame(P, entities),
        num_players=P,
        check_distance=check_distance,
        flush_interval=10_000,
        backend=backend,
        **kw,
    )
    t = script.shape[0] // batches
    for i in range(batches):
        sess.advance_frames(script[i * t : (i + 1) * t])
    return sess


def assert_carry_equal(a, b):
    la = jtu.tree_leaves_with_path(jax.device_get(a))
    lb = jtu.tree_leaves(jax.device_get(b))
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=jtu.keystr(path)
        )


@pytest.mark.parametrize("check_distance,entities", [(2, 1024), (5, 2048)])
def test_tiled_carry_parity_with_xla(check_distance, entities):
    """Multiple tiles (auto tile sizing) through multiple batches: the
    cross-tile checksum accumulation, ring streaming and batch-boundary
    carry must all be bit-identical to the XLA scan."""
    rng = np.random.default_rng(7)
    script = rng.integers(0, 16, size=(36, P, 1), dtype=np.uint8)
    xla = drive("xla", script, entities, check_distance)
    tiled = drive("pallas-tiled-interpret", script, entities, check_distance)
    assert_carry_equal(xla.carry, tiled.carry)
    xla.check()
    tiled.check()


def test_tiled_multi_tile_explicit():
    """Force several tiles explicitly (tile_rows=8 over 16 rows)."""
    from ggrs_tpu.tpu.pallas_tiled import PallasTiledSyncTestCore

    core = PallasTiledSyncTestCore(
        ExGame(P, 2048), P, 3, interpret=True, tile_rows=8
    )
    assert core.n_tiles == 2
    sess = TpuSyncTestSession(
        ExGame(P, 2048), num_players=P, check_distance=3,
        flush_interval=10_000, backend="xla",
    )
    rng = np.random.default_rng(8)
    script = rng.integers(0, 16, size=(14, P, 1), dtype=np.uint8)
    import jax.numpy as jnp

    out = core.batch(sess.carry, jnp.asarray(script))
    sess.advance_frames(script)
    assert_carry_equal(sess.carry, out)


@pytest.mark.parametrize("sharded", [False, True])
def test_tiled_detects_injected_divergence(sharded):
    """Unsharded kernel verdict and the psum'd sharded verdict both latch a
    mismatch injected into (one shard's slice of) the ring."""
    from ggrs_tpu.errors import MismatchedChecksum
    from ggrs_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8) if sharded else None
    rng = np.random.default_rng(9)
    script = rng.integers(0, 16, size=(24, P, 1), dtype=np.uint8)
    sess = TpuSyncTestSession(
        ExGame(P, 2048), num_players=P, check_distance=4,
        flush_interval=10_000, backend="pallas-tiled-interpret", mesh=mesh,
    )
    sess.advance_frames(script[:12])
    sess.check()
    c = sess.current_frame
    ring = dict(sess.carry["ring"])
    slot = (c - 4) % sess.ring_len
    ring["pos"] = ring["pos"].at[slot, 0, 0].add(7)
    sess.carry = {**sess.carry, "ring": ring}
    sess.advance_frames(script[12:])
    with pytest.raises(MismatchedChecksum) as err:
        sess.check()
    # tick c's rollback loads frame c - d from the corrupted slot; the
    # first frame it re-saves, c - d + 1, is where the verdict latches
    assert err.value.frame == c - 4 + 1
    assert int(sess.carry["mismatch_frame"]) == c - 4 + 1


@pytest.mark.parametrize("check_distance", [2, 5])
def test_sharded_tiled_carry_parity(check_distance):
    """The flagship composition: shard_map over the `entity` axis running
    one local tiled kernel per device, partial checksums psum'd. Full-carry
    bit parity vs the SHARDED XLA scan (same mesh) and the UNSHARDED tiled
    kernel across batch boundaries."""
    from ggrs_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)  # (beam=2, entity=4)
    entities = 2048  # 512/shard = 4 rows/shard
    rng = np.random.default_rng(11)
    script = rng.integers(0, 16, size=(36, P, 1), dtype=np.uint8)
    sharded_tiled = drive(
        "pallas-tiled-interpret", script, entities, check_distance, mesh=mesh
    )
    sharded_xla = drive("xla", script, entities, check_distance, mesh=mesh)
    plain_tiled = drive(
        "pallas-tiled-interpret", script, entities, check_distance
    )
    assert_carry_equal(sharded_xla.carry, sharded_tiled.carry)
    assert_carry_equal(plain_tiled.carry, sharded_tiled.carry)
    sharded_tiled.check()
    # the state actually shards: each device holds entities/4 rows
    shard = sharded_tiled.carry["state"]["pos"].addressable_shards[0]
    assert shard.data.shape[0] == entities // mesh.shape["entity"]


HIST_KEYS = ("h_tag", "h_hi", "h_lo", "mismatch", "mismatch_frame")


def scan_oracle(hc, frames, valid, hi, lo):
    """The first-seen verdict as a sequential loop over the events, one
    TpuSyncTestSession._save_and_check per valid event: the rule the
    loop-free post-pass must reproduce bit for bit."""
    tag = np.array(hc["h_tag"], np.int32)
    shi = np.array(hc["h_hi"], np.uint32)
    slo = np.array(hc["h_lo"], np.uint32)
    mismatch, mframe = bool(hc["mismatch"]), int(hc["mismatch_frame"])
    for frame, ok, h_, l_ in zip(frames.tolist(), valid.tolist(),
                                 hi.tolist(), lo.tolist()):
        h = frame % tag.shape[0]
        seen = int(tag[h]) == frame
        differs = ok and seen and (int(shi[h]) != h_ or int(slo[h]) != l_)
        if differs and not mismatch:
            mframe = frame
        mismatch = mismatch or differs
        if ok:
            tag[h] = frame
            if not seen:
                shi[h], slo[h] = h_, l_
    return {"h_tag": tag, "h_hi": shi, "h_lo": slo,
            "mismatch": np.bool_(mismatch), "mismatch_frame": np.int32(mframe)}


def kernel_frames(d, c0, t):
    """Frame and validity of each save event of a t-tick batch from frame
    c0, in the tiled kernel's layout (pallas_tiled module docstring)."""
    frames, valid = [], []
    for c in range(c0, c0 + t):
        for j in range(d):
            frames.append(c - d + 1 + j if j < d - 1 else c)
            valid.append(j == d - 1 or c > d)
    return np.array(frames, np.int32), np.array(valid)


def oracle_verdict(carry, d, weight, parts_hi, parts_lo, c0, t):
    """scan_oracle over the kernel's events, the frame term folded into
    the partial sums at int32 wraparound."""
    frames, valid = kernel_frames(d, c0, t)

    def fold(parts, w):
        return ((parts.reshape(-1).astype(np.int64)
                 + frames.astype(np.int64) * w) & 0xFFFFFFFF).astype(np.uint32)

    return scan_oracle(carry, frames, valid, fold(parts_hi, int(weight)),
                       fold(parts_lo, 1))


def boot_carry(hist):
    return {"h_tag": np.full(hist, -1, np.int32),
            "h_hi": np.zeros(hist, np.uint32),
            "h_lo": np.zeros(hist, np.uint32),
            "mismatch": np.bool_(False), "mismatch_frame": np.int32(-1)}


VERDICT_D = 4


@pytest.mark.parametrize(
    "case",
    [
        # (name, c0, t_ticks, {event index: (hi delta, lo delta)}, carry)
        ("boot", 0, 7, {}, "replay"),
        ("boot_t1", 2, 1, {}, "replay"),
        ("steady", 37, 8, {}, "replay"),
        ("steady_t1", 37, 1, {}, "replay"),
        ("wrap_batch_boundary", 29, 4, {}, "replay"),
        ("diff_first_event", 37, 8, {0: (1, 0)}, "replay"),
        ("diff_middle_event", 37, 8, {16: (0, 3)}, "replay"),
        ("diff_last_event", 37, 8, {31: (5, 5)}, "replay"),
        ("diff_t1", 37, 1, {1: (1, 0)}, "replay"),
        ("diff_two_events", 37, 8, {9: (0, 1), 22: (1, 0)}, "replay"),
        ("carry_tags_foreign", 37, 8, {5: (1, 0)}, "foreign"),
        ("carry_latched", 37, 8, {6: (1, 1)}, "latched"),
    ],
    ids=lambda case: case[0],
)
def test_tiled_verdict_matches_scan_oracle(case):
    """The tiled core's loop-free verdict against the sequential oracle on
    the kernel's own event layout: boot (invalid rollback events), steady
    state, single-tick batches, a batch whose history wraps its slots,
    injected checksum diffs, a carry whose tags match nothing, and a carry
    that already latched. Every output field, exactly."""
    import jax.numpy as jnp

    from ggrs_tpu.tpu.pallas_tiled import PallasTiledSyncTestCore

    _, c0, t, diffs, carry_kind = case
    d = VERDICT_D
    core = PallasTiledSyncTestCore(ExGame(P, 1024), P, d, interpret=True)
    hist, weight = d + 2, core._cs_frame_weight
    rng = np.random.default_rng(24)
    # one true checksum pair per frame, so honest re-saves agree
    truth = rng.integers(-(2**31), 2**31, size=(2, c0 + t + d + 1),
                         dtype=np.int64).astype(np.int32)

    def parts_for(c_start, ticks):
        at = kernel_frames(d, c_start, ticks)[0].reshape(ticks, d) + d
        return truth[0][at], truth[1][at]

    # the history as honest batches from genesis leave it at c0
    carry = oracle_verdict(boot_carry(hist), d, weight, *parts_for(0, c0), 0, c0)
    if carry_kind == "foreign":
        carry["h_tag"] = carry["h_tag"] + 3 * hist
    elif carry_kind == "latched":
        carry["mismatch"], carry["mismatch_frame"] = np.bool_(True), np.int32(5)

    parts_hi, parts_lo = parts_for(c0, t)
    for e, (dh, dl) in diffs.items():
        parts_hi.reshape(-1)[e] += dh
        parts_lo.reshape(-1)[e] += dl

    want = oracle_verdict(carry, d, weight, parts_hi, parts_lo, c0, t)
    want["frame"] = np.int32(c0 + t)
    got = jax.jit(core._verdict, static_argnums=(4,))(
        {k: jnp.asarray(v) for k, v in carry.items()},
        jnp.asarray(parts_hi), jnp.asarray(parts_lo), jnp.int32(c0), t,
    )
    if diffs and carry_kind == "replay" and c0 > d:
        # a diff on a re-saved frame must latch; the events at j = d - 1
        # are first saves, which only store
        assert bool(want["mismatch"]) == any(
            e % d != d - 1 for e in diffs
        )
    for key in HIST_KEYS + ("frame",):
        np.testing.assert_array_equal(
            np.asarray(got[key]), want[key], err_msg=key
        )
        assert np.asarray(got[key]).dtype == want[key].dtype, key


@pytest.mark.parametrize("seed,hist,events", [
    (0, 3, 1), (1, 3, 40), (2, 6, 97), (3, 18, 960), (4, 18, 333),
])
def test_first_seen_verdict_arbitrary_streams(seed, hist, events):
    """Any frame/valid stream — repeats, gaps, out-of-order and negative
    frames, few distinct checksum values so equal and unequal re-saves
    both occur — against the sequential oracle, from a random carry."""
    import jax.numpy as jnp

    from ggrs_tpu.tpu.pallas_tiled import first_seen_verdict

    rng = np.random.default_rng(seed)
    frames = rng.integers(-4, 3 * hist, size=events).astype(np.int32)
    valid = rng.random(events) < 0.8
    hi = rng.integers(0, 3, size=events).astype(np.uint32)
    lo = rng.integers(0, 3, size=events).astype(np.uint32)
    carry = {
        "h_tag": rng.integers(-1, 3 * hist, size=hist).astype(np.int32),
        "h_hi": rng.integers(0, 3, size=hist).astype(np.uint32),
        "h_lo": rng.integers(0, 3, size=hist).astype(np.uint32),
        "mismatch": np.bool_(seed % 2 == 1),
        "mismatch_frame": np.int32(-1 if seed % 2 == 0 else 7),
    }
    want = scan_oracle(carry, frames, valid, hi, lo)
    got = jax.jit(first_seen_verdict)(
        {k: jnp.asarray(v) for k, v in carry.items()},
        jnp.asarray(frames), jnp.asarray(valid), jnp.asarray(hi),
        jnp.asarray(lo),
    )
    for key in HIST_KEYS:
        np.testing.assert_array_equal(
            np.asarray(got[key]), want[key], err_msg=key
        )


def test_tiled_reduce_model_single_tile_only():
    """Arena's per-team centroids are cross-entity reductions: legal on
    the tiled kernel ONLY as one whole-world tile (inline sums complete);
    a shard's slice — where the sums would be silently local — is
    rejected."""
    from ggrs_tpu.models.arena import Arena
    from ggrs_tpu.tpu.pallas_tiled import PallasTiledSyncTestCore

    core = PallasTiledSyncTestCore(Arena(P, 1024), P, 3, interpret=True)
    assert core.n_tiles == 1  # forced whole-world tile
    with pytest.raises(AssertionError, match="shard"):
        PallasTiledSyncTestCore(
            Arena(P, 1024), P, 3, interpret=True, local_entities=512
        )
