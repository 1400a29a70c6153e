"""Replay player: re-simulate a recorded match to bit-identical state.

    python examples/replay.py match.npz [--model ex_game] [--players 2] \
        [--entities 4096]

Recordings come from `examples/ex_game_p2p.py --record match.npz` (or any
code using ggrs_tpu.utils.replay.InputRecorder). The replay runs the
confirmed input stream from the initial world through fused multi-tick
device dispatches — determinism makes the result identical to what every
peer computed live, which this prints as the final digest + checksum.

Forensics (ggrs_tpu.utils.replay composed with utils.checkpoint):
    --save-seek out.npz    persist the final state as a SEEK POINT; a
                           later replay of a longer recording of the same
                           match resumes from it (--seek-from) instead of
                           frame 0
    --seek-from ckpt.npz   resume the replay from a seek point
    --postmortem hist.json desync post-mortem: compare the replay's
                           per-frame checksums against a peer's recorded
                           history (a JSON {frame: combined_checksum}
                           map, e.g. json.dump of
                           session.local_checksum_history) and report the
                           FIRST mismatching frame with both values
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path", help="recording (.npz) to replay")
    ap.add_argument("--model", choices=["ex_game", "arena", "swarm"],
                    default="ex_game")
    ap.add_argument("--players", type=int, default=2)
    ap.add_argument("--entities", type=int, default=4096)
    ap.add_argument("--save-seek", metavar="OUT",
                    help="persist the final state as a replay seek point")
    ap.add_argument("--seek-from", metavar="CKPT",
                    help="resume the replay from a seek point")
    ap.add_argument("--postmortem", metavar="HIST",
                    help="JSON {frame: checksum} peer history to compare")
    args = ap.parse_args()
    from ggrs_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from ggrs_tpu.models import Arena, ExGame, Swarm
    from ggrs_tpu.ops.fixed_point import combine_checksum
    from ggrs_tpu.utils.replay import (
        desync_postmortem,
        load_replay,
        load_seek_checkpoint,
        replay_to_state,
        save_seek_checkpoint,
    )

    model_cls = {"arena": Arena, "swarm": Swarm}.get(args.model, ExGame)
    game = model_cls(args.players, args.entities)
    inputs, statuses = load_replay(args.path, game)
    start_state, start_frame = None, 0
    if args.seek_from:
        start_state, start_frame = load_seek_checkpoint(args.seek_from, game)
        print(f"seeking: resume from checkpointed frame {start_frame}")
    print(f"replaying {inputs.shape[0] - start_frame} confirmed frames "
          f"({args.model}, {args.entities} entities, {args.players} players)")

    if args.postmortem:
        import json

        with open(args.postmortem) as f:
            peer = {int(k): int(v) for k, v in json.load(f).items()}
        verdict = desync_postmortem(
            game, inputs, statuses, peer,
            start_state=start_state, start_frame=start_frame,
        )
        if verdict is None:
            print(f"postmortem: all {len(peer)} recorded checksums agree "
                  "with the replay — no divergence in this recording")
            return 0
        frame, ours, theirs = verdict
        print(f"postmortem: FIRST DIVERGENCE at frame {frame}: "
              f"replay {ours:#034x} vs peer {theirs:#034x}")
        return 2

    t0 = time.perf_counter()
    final = replay_to_state(
        game, inputs, statuses, start_state=start_state,
        start_frame=start_frame,
    )
    import jax
    import numpy as np

    jax.block_until_ready(final)
    hi, lo = jax.device_get(game.checksum(jax.device_put(final)))
    dt = time.perf_counter() - t0
    p0 = np.asarray(final["pos"])[0]
    print(
        f"done in {dt:.3f}s: frame {int(np.asarray(final['frame']))}, "
        f"entity0 @ ({int(p0[0])},{int(p0[1])}), "
        f"checksum {combine_checksum(int(hi), int(lo)):#034x}"
    )
    if args.save_seek:
        save_seek_checkpoint(args.save_seek, final, game)
        print(f"seek point saved: {args.save_seek} "
              f"(frame {int(np.asarray(final['frame']))})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
