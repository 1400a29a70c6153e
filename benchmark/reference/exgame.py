"""Plain reference of the ex_game world, written from the game's description.

It imports nothing of ggrs_tpu and takes nothing the program made. The world
is the reference example's ice physics (GGRS v0.9.4 examples/ex_game,
ex_game.rs:259-321) on an N-entity structure-of-arrays world in integer fixed
point: Q8 sub-pixel positions and velocities, a 16-bit heading, a parabolic
integer sine, an exact integer square root. Entity i follows player i % P.

Every function takes an array module `xp` (numpy on the host, jax.numpy on the
chip): integer arithmetic is exact on both, so the two give the same bits.
`store_bits=16` is the control: the same step with the world stored at int16,
the nearest precision below the configuration's int32.
"""

from __future__ import annotations

import math

import numpy as np

SUBPIX = 256
MAX_X = 600 * SUBPIX
MAX_Y = 800 * SUBPIX
ANGLE_MOD = 1 << 16
MOVE_SPEED = 64
ROT_SPEED = 434
MAX_SPEED = 7 * SUBPIX
FRICTION_NUM = 251
DISCONNECTED = 2  # the input status that substitutes the spin input
DISCONNECT_INPUT = 4
GOLDEN32 = 2654435761
FIELDS = ("px", "py", "vx", "vy", "rot")


def init_world(num_entities: int) -> dict:
    """The ring formation around the arena centre (ex_game.rs:239-248), from
    a 1024-entry Q14 cosine table, as host numpy int32."""
    theta = np.arange(1024, dtype=np.float64) * (2.0 * math.pi / 1024)
    cos_tab = np.round(np.cos(theta) * 16384).astype(np.int64)
    sin_tab = np.round(np.sin(theta) * 16384).astype(np.int64)
    i = np.arange(num_entities, dtype=np.int64)
    base = (i * ANGLE_MOD) // num_entities
    r = 150 * SUBPIX
    return {
        "px": (MAX_X // 2 + ((r * cos_tab[base >> 6]) >> 14)).astype(np.int32),
        "py": (MAX_Y // 2 + ((r * sin_tab[base >> 6]) >> 14)).astype(np.int32),
        "vx": np.zeros(num_entities, np.int32),
        "vy": np.zeros(num_entities, np.int32),
        "rot": ((base + ANGLE_MOD // 2) & (ANGLE_MOD - 1)).astype(np.int32),
        "frame": np.int32(0),
    }


def _sin(a, xp):
    a = a & (ANGLE_MOD - 1)
    h = a & 0x7FFF
    p = (h * (0x8000 - h)) >> 14
    r = p + ((225 * (((p * p) >> 14) - p)) >> 10)
    return xp.where(((a >> 15) & 1) == 1, -r, r)


def _isqrt(n, xp):
    x, c, d = n, xp.zeros_like(n), 1 << 22
    for _ in range(12):
        take = x >= c + d
        x = xp.where(take, x - (c + d), x)
        c = xp.where(take, (c >> 1) + d, c >> 1)
        d >>= 2
    return c


def step(world: dict, inputs, statuses, xp, store_bits: int = 32) -> dict:
    """One frame. `inputs` and `statuses`: int arrays [..., P]; the world's
    fields are [..., N] (a leading axis stacks independent worlds)."""
    n = world["px"].shape[-1]
    p = inputs.shape[-1]
    owner = xp.arange(n) % p
    inp = xp.take(inputs.astype(xp.int32), owner, axis=-1)
    st = xp.take(statuses.astype(xp.int32), owner, axis=-1)
    inp = xp.where(st == DISCONNECTED, DISCONNECT_INPUT, inp)
    up, down = (inp & 1) != 0, (inp & 2) != 0
    left, right = (inp & 4) != 0, (inp & 8) != 0
    rot = world["rot"]
    vx = (world["vx"] * FRICTION_NUM) >> 8
    vy = (world["vy"] * FRICTION_NUM) >> 8
    thrust = xp.where(up & ~down, 1, 0) - xp.where(down & ~up, 1, 0)
    vx = vx + thrust * ((MOVE_SPEED * _sin(rot + ANGLE_MOD // 4, xp)) >> 14)
    vy = vy + thrust * ((MOVE_SPEED * _sin(rot, xp)) >> 14)
    turn = xp.where(left & ~right, -ROT_SPEED, 0) + xp.where(
        right & ~left, ROT_SPEED, 0
    )
    rot = (rot + turn) & (ANGLE_MOD - 1)
    m2 = vx * vx + vy * vy
    over = m2 > MAX_SPEED * MAX_SPEED
    mag = _isqrt(m2, xp)
    mag = xp.where(mag == 0, 1, mag)
    vx = xp.where(over, (vx * MAX_SPEED) // mag, vx)
    vy = xp.where(over, (vy * MAX_SPEED) // mag, vy)
    out = {
        "px": xp.clip(world["px"] + vx, 0, MAX_X),
        "py": xp.clip(world["py"] + vy, 0, MAX_Y),
        "vx": vx,
        "vy": vy,
        "rot": rot,
    }
    store = xp.int16 if store_bits == 16 else xp.int32
    out = {k: v.astype(store).astype(xp.int32) for k, v in out.items()}
    out["frame"] = world["frame"] + 1
    return out


def checksum(world: dict, xp):
    """(hi, lo) uint32: over the words pos (x, y interleaved), vel (likewise),
    rot, frame, hi = sum(word_i * (i + 1) * GOLDEN32), lo = sum(word_i), both
    mod 2**32. Fields are [..., N]; sums run over the last axis."""
    n = world["px"].shape[-1]
    u = xp.uint32
    e = xp.arange(n, dtype=u)
    g = u(GOLDEN32)
    hi, lo = u(0), u(0)
    for word0, field, stride in (
        (1, "px", 2), (2, "py", 2), (2 * n + 1, "vx", 2), (2 * n + 2, "vy", 2),
        (4 * n + 1, "rot", 1),
    ):
        w = world[field].astype(u)
        idx = e * u(stride) + u(word0)
        hi = hi + xp.sum(w * (idx * g), axis=-1, dtype=u)
        lo = lo + xp.sum(w, axis=-1, dtype=u)
    f = xp.asarray(world["frame"]).astype(u)
    hi = hi + f * (u(5 * n + 1) * g)
    lo = lo + f
    return hi, lo


def combine(hi, lo) -> int:
    """The program's 64-bit checksum value: hi in the upper word."""
    return (int(hi) << 32) | int(lo)


def to_program_layout(world: dict) -> dict:
    """{frame, pos[N,2], vel[N,2], rot[N]} numpy, to compare with the
    program's state bytes."""
    w = {k: np.asarray(v) for k, v in world.items()}
    return {
        "frame": np.int32(w["frame"]),
        "pos": np.stack([w["px"], w["py"]], axis=-1),
        "vel": np.stack([w["vx"], w["vy"]], axis=-1),
        "rot": w["rot"],
    }
