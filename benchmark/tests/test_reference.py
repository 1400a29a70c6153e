"""The plain reference against a second witness: the program's own numpy
oracle (ggrs_tpu.models.ex_game.step_oracle) on the CPU, bit for bit; and
jax.numpy against numpy. The int16 control departs."""

import numpy as np

from benchmark.reference import exgame as ref


def _drive(n, players, frames, seed, store_bits=32):
    from ggrs_tpu.models.ex_game import checksum_oracle, init_oracle, step_oracle

    rng = np.random.default_rng(seed)
    a, b = init_oracle(players, n), ref.init_world(n)
    st = np.zeros(players, np.int32)
    with np.errstate(over="ignore"):
        for f in range(frames):
            inp = rng.integers(0, 16, players).astype(np.uint8)
            if f == frames // 2:
                st[-1] = ref.DISCONNECTED
            a = step_oracle(a, inp, st, players)
            b = ref.step(b, inp, st, np, store_bits)
        return a, b, checksum_oracle(a), tuple(int(x) for x in ref.checksum(b, np))


def test_reference_matches_program_oracle():
    for n, players in ((4096, 2), (1024, 4)):
        a, b, ca, cb = _drive(n, players, 200, n)
        lay = ref.to_program_layout(b)
        assert all(np.array_equal(a[k], lay[k]) for k in a)
        assert ca == cb


def test_int16_control_departs():
    a, b, ca, cb = _drive(4096, 2, 30, 1, store_bits=16)
    assert ca != cb


def test_jax_numpy_matches_numpy():
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    w_np, w_j = ref.init_world(512), None
    w_j = {k: jnp.asarray(v) for k, v in w_np.items()}
    st = np.zeros(2, np.int32)
    with np.errstate(over="ignore"):
        for _ in range(40):
            inp = rng.integers(0, 16, 2).astype(np.int32)
            w_np = ref.step(w_np, inp, st, np)
            w_j = ref.step(w_j, jnp.asarray(inp), jnp.asarray(st), jnp)
        assert all(np.array_equal(np.asarray(w_j[k]), w_np[k]) for k in w_np)
        assert (tuple(int(x) for x in ref.checksum(w_j, jnp))
                == tuple(int(x) for x in ref.checksum(w_np, np)))
