"""P2P driver (reference: examples/ex_game/ex_game_p2p.rs).

Runs one side of a 2-player session over real UDP with a 60fps accumulator
loop, slowing 10% when ahead of the remote (the reference's throttling,
ex_game_p2p.rs:91-94). Start both sides:

    python examples/ex_game_p2p.py --local-port 7000 --players localhost:7001 local --handle 0 &
    python examples/ex_game_p2p.py --local-port 7001 --players local localhost:7000 --handle 1

`--players` takes one entry per handle: `local` or `host:port`.
Spectators attach with `--spectators host:port ...`.
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from examples.ex_game_common import FPS, HostGame, scripted_input
from ggrs_tpu import (
    NotSynchronized,
    PlayerType,
    PredictionThreshold,
    SessionBuilder,
    SessionState,
)
from ggrs_tpu.errors import GGRSError
from ggrs_tpu.network.sockets import UdpNonBlockingSocket


def parse_addr(s: str):
    import socket

    host, port = s.rsplit(":", 1)
    # sessions route inbound packets by exact address equality, and UDP
    # receive reports numeric IPs — so resolve hostnames up front
    return (socket.gethostbyname(host), int(port))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--local-port", type=int, required=True)
    ap.add_argument("--players", nargs="+", required=True)
    ap.add_argument("--spectators", nargs="*", default=[])
    ap.add_argument("--frames", type=int, default=600)
    ap.add_argument("--input-delay", type=int, default=2)
    ap.add_argument("--entities", type=int, default=4096)
    ap.add_argument(
        "--native",
        action="store_true",
        help="run on the C++ session core (requires `make -C native`)",
    )
    ap.add_argument(
        "--tpu",
        action="store_true",
        help="fulfill requests on the device backend (one fused dispatch "
        "per tick) instead of the numpy host oracle",
    )
    ap.add_argument(
        "--beam",
        type=int,
        default=0,
        help="with --tpu: speculative input-beam width (0 = off); the "
        "speculation launch runs in loop idle time and stands down "
        "automatically when the frame budget cannot absorb its cost",
    )
    ap.add_argument(
        "--lazy-ticks",
        type=int,
        default=0,
        help="with --tpu: buffer up to N ticks per fused device dispatch "
        "(amortizes the per-program dispatch floor; the periodic digest "
        "still flushes, so rendering-style loops behave per-tick)",
    )
    ap.add_argument(
        "--auth-key",
        default=None,
        help="32 hex chars: authenticate every datagram (SipHash-2-4); all "
        "peers must share the key",
    )
    ap.add_argument(
        "--replay-protect",
        action="store_true",
        help="with --auth-key: drop replayed datagrams too (all peers must "
        "enable it together)",
    )
    ap.add_argument(
        "--transport",
        choices=("udp", "tcp"),
        default="udp",
        help="L1 transport: udp (default) or the TCP-backed datagram "
        "socket (the pluggable-transport seam; all peers must match)",
    )
    ap.add_argument(
        "--record",
        default=None,
        metavar="PATH",
        help="record the match: the confirmed input stream saves to PATH "
        "at exit (replay with examples/replay.py — bit-identical)",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="PATH",
        help="drive local players from a recorded human input trace (JSON "
        "{fps, players: [[byte,...],...]}; see examples/traces/) instead "
        "of the scripted stream — the latency-demo configuration "
        "(reference analog: the playable ex_game_p2p.rs driver)",
    )
    ap.add_argument(
        "--budget-report",
        action="store_true",
        help="at exit, print per-frame critical-path latency stats and "
        "the 60fps frame-budget hit rate as one JSON line",
    )
    args = ap.parse_args()
    from ggrs_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    trace = None
    if args.trace:
        import json as _json

        with open(args.trace) as fh:
            trace = _json.load(fh)
        assert trace.get("players"), "trace has no player streams"
    if args.replay_protect and not args.auth_key:
        ap.error("--replay-protect requires --auth-key")

    builder = (
        SessionBuilder(input_size=1)
        .with_num_players(len(args.players))
        .with_input_delay(args.input_delay)
        .with_fps(FPS)
    )
    if args.native:
        builder = builder.with_native_sessions(True)
    local_handles = []
    for handle, spec in enumerate(args.players):
        if spec == "local":
            builder = builder.add_player(PlayerType.local(), handle)
            local_handles.append(handle)
        else:
            builder = builder.add_player(PlayerType.remote(parse_addr(spec)), handle)
    for i, spec in enumerate(args.spectators):
        builder = builder.add_player(
            PlayerType.spectator(parse_addr(spec)), len(args.players) + i
        )

    if args.tpu:
        from ggrs_tpu.models.ex_game import ExGame
        from ggrs_tpu.tpu import TpuRollbackBackend

        backend = TpuRollbackBackend(
            ExGame(len(args.players), args.entities),
            max_prediction=builder.max_prediction,
            num_players=len(args.players),
            beam_width=args.beam,
            # real-time loop: launch speculation from idle time, stand
            # down when the budget can't absorb it, and batch ticks when
            # nothing needs device results between digests
            speculation_gate="adaptive",
            defer_speculation=bool(args.beam),
            lazy_ticks=args.lazy_ticks,
        )
        # compile before the session even exists: the first jit would stall
        # the 60fps loop past the peers' disconnect timeout
        backend.warmup()

    if args.transport == "tcp":
        from ggrs_tpu.network.tcp_socket import TcpDatagramSocket

        sock = TcpDatagramSocket(args.local_port)
    else:
        sock = UdpNonBlockingSocket(args.local_port)
    if args.auth_key:
        from ggrs_tpu.network.auth import AuthenticatedSocket

        sock = AuthenticatedSocket(
            sock, bytes.fromhex(args.auth_key), replay_protect=args.replay_protect
        )
    sess = builder.start_p2p_session(sock)
    recorder = None
    if args.record:
        from ggrs_tpu.utils.replay import InputRecorder

        recorder = InputRecorder()
    if args.tpu:

        class DeviceGameDriver:
            handle_requests = staticmethod(backend.handle_requests)

            @staticmethod
            def digest() -> str:
                st = backend.state_numpy()
                p0 = st["pos"][0]
                hits = (
                    f" beam {backend.beam_hits}+{backend.beam_partial_hits}p"
                    f"/{backend.beam_hits + backend.beam_partial_hits + backend.beam_misses}"
                    f" served {backend.rollback_frames_adopted}"
                    f"/{backend.rollback_frames} gated {backend.beam_gated}"
                    if args.beam
                    else ""
                )
                return (
                    f"frame {int(st['frame']):5d} entity0 @ "
                    f"({int(p0[0])},{int(p0[1])}){hits}"
                )

        game = DeviceGameDriver()
    else:
        game = HostGame(len(args.players), args.entities)

    def local_input(frame: int, handle: int) -> bytes:
        if trace is not None:
            stream = trace["players"][handle % len(trace["players"])]
            return bytes([stream[frame % len(stream)] & 0x0F])
        return scripted_input(frame, handle)

    # accumulator loop (ex_game_p2p.rs:80-129)
    frame = 0
    last = time.perf_counter()
    accumulator = 0.0
    frame_ms = []  # per-frame critical-path time (inputs -> requests done)
    skipped = 0  # prediction-threshold stalls (remote too far behind)
    wall_t0 = time.perf_counter()
    while frame < args.frames:
        now = time.perf_counter()
        accumulator += now - last
        last = now

        # run slower when ahead so remotes can catch up
        fps_delta = 1.0 / FPS
        if sess.frames_ahead_estimate() > 0:
            fps_delta *= 1.1

        sess.poll_remote_clients()
        for event in sess.events():
            print("event:", event)

        while accumulator > fps_delta:
            accumulator -= fps_delta
            if sess.current_state() != SessionState.RUNNING:
                continue
            try:
                t0 = time.perf_counter()
                for handle in local_handles:
                    sess.add_local_input(handle, local_input(frame, handle))
                reqs = sess.advance_frame()
                if recorder is not None:
                    recorder.observe(reqs)
                game.handle_requests(reqs)
                frame_ms.append((time.perf_counter() - t0) * 1000.0)
                frame += 1
                if frame % 120 == 0:
                    print(game.digest())
            except PredictionThreshold:
                skipped += 1  # skip a frame; remote is behind
            except NotSynchronized:
                pass
        if args.tpu and args.beam:
            # idle-time work: the deferred speculation launch happens after
            # the frame's critical path, exactly where a renderer would be
            backend.launch_pending_speculation()
        time.sleep(0.001)

    wall_s = time.perf_counter() - wall_t0
    print("done:", game.digest())
    if args.budget_report and frame_ms:
        import json as _json

        xs = sorted(frame_ms)
        q = lambda p: round(xs[min(int(p * len(xs)), len(xs) - 1)], 3)
        budget = 1000.0 / FPS
        print(
            _json.dumps(
                {
                    "frames": len(xs),
                    "budget_ms": round(budget, 3),
                    # the latency-demo headline: fraction of frames whose
                    # critical path (input ingest -> session advance ->
                    # request fulfillment dispatch) fit the 60fps budget
                    "budget_hit_rate": round(
                        sum(x <= budget for x in xs) / len(xs), 4
                    ),
                    "frame_p50_ms": q(0.50),
                    "frame_p95_ms": q(0.95),
                    "frame_p99_ms": q(0.99),
                    "frame_max_ms": round(xs[-1], 3),
                    "skipped_frames": skipped,
                    "achieved_fps": round(len(xs) / wall_s, 1),
                    "trace": args.trace or "scripted",
                }
            ),
            flush=True,
        )
    if recorder is not None:
        from ggrs_tpu.models.ex_game import ExGame as _ExGame

        recorder.confirm_through(sess.confirmed_frame() - 1)
        try:
            # both paths simulate ex_game dynamics (HostGame is its numpy
            # oracle), so the identity stamp is always ExGame-shaped —
            # replays against the wrong world must refuse loudly
            recorder.save(
                args.record,
                game=_ExGame(len(args.players), args.entities),
            )
            print(
                f"recorded {recorder.confirmed_frames} confirmed frames -> "
                f"{args.record}"
            )
        except ValueError:
            print("no confirmed frames at exit; nothing recorded")
    return 0


if __name__ == "__main__":
    sys.exit(main())
