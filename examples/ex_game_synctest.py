"""SyncTest driver (reference: examples/ex_game/ex_game_synctest.rs).

Runs the flagship 4096-entity world under the determinism harness: every
frame rolls back `--check-distance` frames, resimulates on device in one
fused dispatch, and compares checksums against history.

    python examples/ex_game_synctest.py --frames 300 --check-distance 7
    python examples/ex_game_synctest.py --host   # numpy request-by-request
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from examples.ex_game_common import HostGame, scripted_input
from ggrs_tpu import MismatchedChecksum, SessionBuilder


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--players", type=int, default=2)
    ap.add_argument("--frames", type=int, default=300)
    ap.add_argument("--check-distance", type=int, default=7)
    ap.add_argument("--max-prediction", type=int, default=8)
    ap.add_argument("--input-delay", type=int, default=0)
    ap.add_argument("--entities", type=int, default=4096)
    ap.add_argument("--host", action="store_true", help="numpy host path instead of TPU")
    ap.add_argument(
        "--native",
        action="store_true",
        help="run on the C++ session core (requires `make -C native`)",
    )
    ap.add_argument(
        "--model",
        choices=["ex_game", "arena", "swarm"],
        default="ex_game",
        help="which model family to run (device path only)",
    )
    ap.add_argument(
        "--fused",
        choices=["xla", "pallas", "pallas-tiled"],
        default=None,
        help="run the FULLY-FUSED device session (60 ticks per dispatch, "
        "ring/history/verdict device-resident) on the chosen kernel "
        "instead of the per-tick request path",
    )
    ap.add_argument(
        "--device-verify",
        action="store_true",
        help="request path: keep the SyncTest checksum history and verdict "
        "on device (zero readbacks until the final check)",
    )
    args = ap.parse_args()
    from ggrs_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.fused and (args.host or args.native or args.device_verify):
        ap.error(
            "--fused bypasses the request path entirely; it cannot combine "
            "with --host, --native or --device-verify"
        )
    if args.fused == "pallas-tiled" and args.model == "arena":
        ap.error(
            "arena's cross-entity centroids are not tileable; use --fused "
            "pallas or --fused xla for the arena family"
        )
    if args.device_verify and (args.host or args.native):
        ap.error(
            "--device-verify needs the device backend (the verdict lives on "
            "device); it cannot combine with --host or --native"
        )

    if args.fused:
        return run_fused(args)

    builder = (
        SessionBuilder(input_size=1)
        .with_num_players(args.players)
        .with_max_prediction_window(args.max_prediction)
        .with_check_distance(args.check_distance)
        .with_input_delay(args.input_delay)
    )
    if args.native:
        builder = builder.with_native_sessions(True)
    if args.device_verify:
        builder = builder.with_device_checksum_verification()
    sess = builder.start_synctest_session()

    if args.host:
        game = HostGame(args.players, args.entities)
        digest = game.digest
    else:
        from ggrs_tpu.models import Arena, ExGame, Swarm
        from ggrs_tpu.tpu import TpuRollbackBackend

        model_cls = {"arena": Arena, "swarm": Swarm}.get(args.model, ExGame)
        game = TpuRollbackBackend(
            model_cls(args.players, args.entities),
            max_prediction=args.max_prediction,
            num_players=args.players,
            device_verify=args.device_verify,
        )

        def digest() -> str:
            st = game.state_numpy()
            p0 = st["pos"][0]
            extra = f" hp0={int(st['hp'][0])}" if "hp" in st else ""
            return (
                f"frame {int(st['frame']):5d} entity0 @ "
                f"({int(p0[0])},{int(p0[1])}){extra}"
            )

    t0 = time.perf_counter()
    try:
        for frame in range(args.frames):
            for handle in range(args.players):
                sess.add_local_input(handle, scripted_input(frame, handle))
            game.handle_requests(sess.advance_frame())
            if frame % 60 == 0:
                print(digest())
        if args.device_verify:
            game.check()  # the run's single device readback
    except MismatchedChecksum as exc:
        print(f"DESYNC: {exc}")
        return 1
    dt = time.perf_counter() - t0
    resim = args.frames * args.check_distance
    print(
        f"ok: {args.frames} frames, {resim} rollback-frames resimulated in "
        f"{dt:.3f}s ({resim / dt:.0f} frames/s)"
    )
    return 0


def run_fused(args) -> int:
    """The fully-fused session: batches of 60 ticks per device dispatch."""
    import jax
    import numpy as np

    from ggrs_tpu.models import Arena, ExGame, Swarm
    from ggrs_tpu.tpu import TpuSyncTestSession

    model_cls = {"arena": Arena, "swarm": Swarm}.get(args.model, ExGame)
    sess = TpuSyncTestSession(
        model_cls(args.players, args.entities),
        num_players=args.players,
        check_distance=args.check_distance,
        input_delay=args.input_delay,
        flush_interval=60,
        backend=args.fused,
    )
    batch = 60
    script = np.zeros((args.frames, args.players, 1), dtype=np.uint8)
    for f in range(args.frames):
        for h in range(args.players):
            script[f, h, 0] = scripted_input(f, h)[0]
    t0 = time.perf_counter()
    try:
        for start in range(0, args.frames, batch):
            sess.advance_frames(script[start : start + batch])
        sess.check()
        jax.block_until_ready(sess.carry["state"])
    except MismatchedChecksum as exc:
        print(f"DESYNC: {exc}")
        return 1
    dt = time.perf_counter() - t0
    st = sess.state_numpy()
    resim = args.frames * args.check_distance
    print(
        f"fused[{args.fused}] frame {int(st['frame'])}: {resim} "
        f"rollback-frames in {dt:.3f}s ({resim / dt:.0f} frames/s)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
