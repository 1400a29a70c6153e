"""Native (C++) kernel parity vs the pure-Python format oracle.

The shared library is built before collection (tests/conftest.py); every
property is checked byte-for-byte against ggrs_tpu.network.compression and
ggrs_tpu.ops.fixed_point.
"""

import os
import random

import numpy as np
import pytest

from ggrs_tpu.network import compression as pycomp
from ggrs_tpu.ops import fixed_point as fx


@pytest.fixture(scope="module")
def native():
    from ggrs_tpu import native as nat

    if not nat.available():
        if os.path.exists(nat._LIB_PATH):
            pytest.fail("native library built but failed to load")
        pytest.skip("native library not built (make -C native)")
    return nat


def _cases(rng, count=200):
    for _ in range(count):
        n = rng.randrange(0, 600)
        yield bytes(
            rng.choice([0, 0, 0, 0xFF, 0xFF, rng.randrange(256)]) for _ in range(n)
        )


def test_rle_encode_matches_python_exactly(native):
    rng = random.Random(1)
    for data in _cases(rng):
        assert native.rle_encode(data) == pycomp.rle_encode(data)


def test_rle_decode_roundtrip_and_cross(native):
    rng = random.Random(2)
    for data in _cases(rng):
        enc_native = native.rle_encode(data)
        # native decodes python's encoding and vice versa
        assert native.rle_decode(pycomp.rle_encode(data)) == data
        assert pycomp.rle_decode(enc_native) == data


def test_delta_matches_python(native):
    rng = random.Random(3)
    for _ in range(100):
        m = rng.randrange(1, 33)
        k = rng.randrange(1, 20)
        ref = bytes(rng.randrange(256) for _ in range(m))
        pending = [bytes(rng.randrange(256) for _ in range(m)) for _ in range(k)]
        assert native.delta_encode(ref, pending) == pycomp.delta_encode(ref, pending)
        data = pycomp.delta_encode(ref, pending)
        assert native.delta_decode(ref, data) == pycomp.delta_decode(ref, data)


def test_full_codec_cross_implementation(native):
    rng = random.Random(4)
    ref = bytes(rng.randrange(256) for _ in range(8))
    pending = [bytes(rng.randrange(256) for _ in range(8)) for _ in range(32)]
    # python-encoded stream decodes identically through the native path
    wire = pycomp.rle_encode(pycomp.delta_encode(ref, pending))
    assert native.delta_decode(ref, native.rle_decode(wire)) == pending


def test_malformed_rle_rejected(native):
    with pytest.raises(ValueError):
        native.rle_decode(b"\x83")  # truncated varint
    with pytest.raises(ValueError):
        native.rle_decode(b"\x0c\xaa")  # literal run longer than stream


def test_weighted_checksum_matches_python(native):
    rng = np.random.default_rng(5)
    for n in (0, 1, 7, 1024):
        words = rng.integers(0, 2**32, size=n, dtype=np.uint32)
        with np.errstate(over="ignore"):
            hi, lo = fx.weighted_checksum(words, np)
        nhi, nlo = native.weighted_checksum_bytes(words.tobytes())
        assert (int(hi), int(lo)) == (nhi, nlo)
