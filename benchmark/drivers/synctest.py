"""SyncTest driver: the fused determinism check, `TpuSyncTestSession` fed
60-frame `advance_frames` batches of seeded inputs, its device verdict read
by `check()` every `check_every` batches (as a developer's loop would). The
timed entries are `advance_frames` and `check`.

Correctness: the plain reference (benchmark/reference/exgame.py, jax.numpy
on the default device, after the window) replays every frame from genesis
and the session's final world, snapshot ring, checksum history and input
ring must equal it bit for bit, with no mismatch latched. The resimulated
frames feed the frontier bit for bit, so none of that shows whether the
rollbacks ran, or ran `check_distance` deep: a depth probe after it does.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic_gen
from benchmark.reference import exgame as ref


class Cell:
    def __init__(self, config, traffic, *, seed, devices, sizes):
        self.cfg, self.tr, self.seed = config, traffic, seed
        self.devices = devices
        self.players = config["num_players"]
        self.entities = sizes.get("entities", config["num_entities"])
        self.d = traffic["check_distance"]
        self.batch = traffic["batch_frames"]
        self.backend = sizes.get("backend", traffic["backend"])
        self.table = traffic_gen.synctest_batches(
            seed, self.players, self.batch, traffic["distinct_batches"]
        )
        self.batches = list(self.table)

    def setup(self) -> None:
        from ggrs_tpu.models.ex_game import ExGame
        from ggrs_tpu.tpu import TpuSyncTestSession

        mesh = None
        self.entity_shards = 1
        if len(self.devices) > 1:
            from ggrs_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(len(self.devices))
            self.entity_shards = mesh.shape["entity"]
        self.sess = TpuSyncTestSession(
            ExGame(self.players, self.entities), num_players=self.players,
            check_distance=self.d, backend=self.backend, mesh=mesh,
        )
        self.n = 0
        for _ in range(self.tr["warm_batches"]):
            self._advance()
        self.sess.check()

    def _advance(self) -> None:
        self.sess.advance_frames(self.batches[self.n % len(self.batches)])
        self.n += 1

    def window(self, seconds: float, tracing) -> dict:
        every = self.tr["check_every"]
        n0 = self.n
        clock = time.perf_counter
        t0 = clock()
        end = t0 + seconds
        while clock() < end:
            with tracing.span("bench/advance_frames"):
                for _ in range(every):
                    tracing.tick(clock() - t0, batches=1, frames=self.batch)
                    self._advance()
            with tracing.span("bench/check"):
                self.sess.check()
        tracing.stop(sync=self.sess.block_until_ready)
        self.sess.block_until_ready()
        window_s = clock() - t0
        batches = self.window_batches = self.n - n0
        return {
            "window_s": window_s,
            "batches": batches,
            "frames": batches * self.batch,
            "check_distance": self.d,
            "entities": self.entities,
            "ring_len": self.sess.ring_len,
            "players": self.players,
            "backend": self.sess.backend,
            "entity_shards": self.entity_shards,
        }

    # ------------------------------------------------------------------
    # correctness

    def _replay(self, frames: int, store_bits: int):
        """The reference from genesis to `frames` on the default device:
        the final world, the last max(ring_len, d + 2) worlds and their
        checksums; the world of frame `frames - d` is kept whole for the
        depth probe."""
        import jax
        import jax.numpy as jnp

        table = jnp.asarray(self.table[..., 0].astype(np.int32))
        nb, b = table.shape[0], table.shape[1]
        statuses = jnp.zeros((self.players,), jnp.int32)

        def inputs_of(f):
            return table[(f // b) % nb, f % b]

        def body(f, w):
            return ref.step(w, inputs_of(f), statuses, jnp, store_bits)

        advance = jax.jit(lambda w, f0, n: jax.lax.fori_loop(f0, f0 + n, body, w))
        one = jax.jit(lambda w, f: (ref.checksum(w, jnp), body(f, w)))
        world = {k: jnp.asarray(v) for k, v in ref.init_world(self.entities).items()}
        keep = max(self.sess.ring_len, self.d + 2)
        head = max(0, frames - keep)
        world = advance(world, 0, head)
        kept = {}
        for f in range(head, frames):
            (hi, lo), nxt = one(world, f)
            host = jax.device_get(world)
            if f == frames - self.d and store_bits == 32:
                self.probe_base = host
            kept[f] = (ref.to_program_layout(host), (int(hi), int(lo)))
            world = nxt
        return ref.to_program_layout(jax.device_get(world)), kept

    def _depth_probe(self) -> int:
        """1 unless the timed kernel resimulates the cell's full
        check_distance d: add 1 to one entity's heading in the ring slot
        that the next frame c's rollback loads (frame c - d), advance one
        batch, and require the mismatch latched at frame c - d + 1, the
        first frame that rollback re-saves. The entity is the first whose
        change the reference, stepping its own world of frame c - d, sees
        in the checksum of c - d + 1. A rollback that is skipped, or
        shallower, loads an untouched slot and latches nothing. The
        session is left diverged, so this runs after every other
        comparison."""
        import jax

        sess, c, d = self.sess, self.sess.current_frame, self.d
        carry = sess.carry
        if c <= d or bool(carry["mismatch"]):
            return 1
        base = self.probe_base
        f = c - d
        inputs = self.table[(f // self.batch) % len(self.table), f % self.batch, :, 0]
        statuses = np.zeros(self.players, np.int32)
        with np.errstate(over="ignore"):
            want = ref.checksum(ref.step(base, inputs, statuses, np), np)
            for e in range(self.entities):
                moved = {**base, "rot": base["rot"].copy()}
                moved["rot"][e] += 1
                if ref.checksum(ref.step(moved, inputs, statuses, np), np) != want:
                    break
            else:
                return 1
        rot = carry["ring"]["rot"]
        flipped = jax.device_put(rot.at[f % (d + 2), e].add(1), rot.sharding)
        sess.carry = {**carry, "ring": {**carry["ring"], "rot": flipped}}
        self._advance()
        latched = bool(sess.carry["mismatch"])
        return int(not (latched and int(sess.carry["mismatch_frame"]) == c - d + 1))

    def check(self, control: bool = False):
        import jax

        carry = jax.device_get(self.sess.carry)
        frames = self.sess.current_frame
        want_final, want_kept = self._replay(frames, 32)
        ring_frames = range(max(0, frames - self.sess.ring_len), frames)
        if control:
            got_final, got_kept = self._replay(frames, 16)
        else:
            got_final = {k: carry["state"][k] for k in want_final}
            got_kept = {}
            for f in ring_frames:
                s = f % self.sess.ring_len
                h = f % self.sess.hist_len
                world = {k: carry["ring"][k][s] for k in want_final}
                tag = int(carry["h_tag"][h])
                sums = ((int(carry["h_hi"][h]), int(carry["h_lo"][h]))
                        if tag == f else None)
                got_kept[f] = (world, sums)

        def words_wrong(a, b):
            return sum(int(np.sum(np.asarray(a[k]) != np.asarray(b[k])))
                       for k in b)

        state_wrong = words_wrong(got_final, want_final)
        ring_wrong = sum(words_wrong(got_kept[f][0], want_kept[f][0])
                         for f in ring_frames)
        sums_wrong = sum(got_kept[f][1] != want_kept[f][1] for f in ring_frames)
        inputs_wrong = 0
        span = self.sess.ring_len
        for f in range(max(0, frames - span), frames):
            want = self.table[(f // self.batch) % len(self.table), f % self.batch]
            inputs_wrong += int(np.sum(carry["input_ring"][f % span] != want))
        latched = int(bool(carry["mismatch"]))
        compared = {
            "state_words_wrong": (state_wrong, 0),
            "ring_words_wrong": (ring_wrong, 0),
            "checksums_wrong": (sums_wrong, 0),
            "inputs_wrong": (inputs_wrong, 0),
            "mismatch_latched": (latched, 0),
            "frames_wrong": (int(int(carry["frame"]) != frames), 0),
            "depth_probe_missed": (self._depth_probe(), 0),
        }
        self.checked = {"frames": frames, "ring_frames": len(ring_frames)}
        bad = any(v > lim for v, lim in compared.values())
        attempted = self.window_batches
        return attempted, attempted if bad else 0, compared
