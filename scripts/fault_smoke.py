#!/usr/bin/env python
"""Device-domain fault-tolerance smoke gate (scripts/check.sh
--fault-smoke): a seeded FaultPlan firing >= 1 of EVERY fault kind —
dispatch raise, harvest timeout, mailbox overflow storm, checkpoint
corruption, injected slot bit-flip — against a lossy 16-session
resident fleet under GGRS_SANITIZE=1:

  1. SURVIVORS KEEP SERVING: every non-victim session advances through
     the whole run with ZERO desyncs among survivors — one poisoned
     slot costs exactly that slot;
  2. CONTAINMENT IS TYPED: every quarantine surfaces as a SlotPoisoned
     with a forensics bundle on disk, the injected SDC bit-flip is the
     one the audit lane catches (reason sdc_audit, within its sampling
     bound), and the corrupted checkpoint is detected as typed
     CheckpointIncompatible at restore;
  3. RECOMPILE-CLEAN: warmup compiles the megabatch grid + driver +
     audit programs; the faulted serve afterwards compiles NOTHING and
     the jit cache stays within dispatch_bucket_budget();
  4. the fault instruments (ggrs_faults_injected_total,
     ggrs_slot_quarantines_total, ggrs_sdc_audits_total,
     ggrs_sdc_mismatches_total, ggrs_invariant_trips_total) export
     through BOTH exporters.

Runs on CPU (JAX_PLATFORMS=cpu, self-applied) in under a minute. Exits
nonzero with a reason on any failure.
"""

import os
import re
import sys
import tempfile

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("GGRS_SANITIZE", "1")

from ggrs_tpu import enable_global_telemetry  # noqa: E402
from ggrs_tpu.obs import GLOBAL_TELEMETRY  # noqa: E402

SESSIONS = 16
TICKS = 70
SEED = 5
AUDIT_EVERY = 2


def fail(reason):
    print(f"fault-smoke FAIL: {reason}")
    sys.exit(1)


def validate_prometheus(text):
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_:]*="(\\.|[^"\\])*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'
        r" -?[0-9.eE+-]+$"
    )
    comment = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?$")
    for line in text.strip().splitlines():
        ok = comment.match(line) if line.startswith("#") else sample.match(line)
        if not ok:
            fail(f"unparseable prometheus line: {line!r}")
    return text


def main():
    import jax  # noqa: F401

    dump_dir = tempfile.mkdtemp(prefix="ggrs_fault_smoke_")
    enable_global_telemetry(dump_dir=dump_dir)

    import ggrs_tpu.tpu  # noqa: F401  (installs the GGRS_SANITIZE wrapper)
    from ggrs_tpu.analysis.sanitize import active_sanitizer
    from ggrs_tpu.errors import CheckpointIncompatible, SlotPoisoned
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.network.sockets import InMemoryNetwork
    from ggrs_tpu.serve import SessionHost
    from ggrs_tpu.serve.faults import FAULT_KINDS, FaultInjector, FaultPlan
    from ggrs_tpu.serve.loadgen import (
        FRAME_MS,
        build_matches,
        make_scripts,
        sync_fleet,
    )
    from ggrs_tpu.utils.checkpoint import load_device_checkpoint
    from ggrs_tpu.utils.clock import FakeClock

    san = active_sanitizer()
    if san is None:
        fail("sanitizer not installed (GGRS_SANITIZE=1 expected)")

    clock = FakeClock()
    # lossy wire + device faults composing: the victim match is the
    # blast radius, the lossy survivors the control group
    net = InMemoryNetwork(
        clock, latency_ms=20, jitter_ms=6, loss=0.02, seed=SEED
    )
    host = SessionHost(
        ExGame(num_players=4, num_entities=16),
        max_prediction=8, num_players=4, max_sessions=SESSIONS + 4,
        clock=clock, idle_timeout_ms=0, warmup=True,
        resident=True, resident_ticks=8,
        max_inflight_rows=4 * (SESSIONS + 4),
        sdc_audit_every=AUDIT_EVERY,
    )
    matches = build_matches(host, net, clock, sessions=SESSIONS, seed=SEED)
    sync_fleet(host, matches, clock)

    plan = FaultPlan.smoke(SEED, TICKS, persist_dispatch=True)
    corrupt_ticks = [
        f.tick for f in plan.all_faults() if f.kind == "checkpoint_corrupt"
    ]
    # two victim matches: a quarantine wedges its match's survivors at
    # the prediction gate, so later faults need an unwedged pool
    victims = matches[0] + matches[1]
    injector = FaultInjector(host, plan, victims=victims).install()

    base_recompiles = len(san.recompiles)
    ckpt = os.path.join(dump_dir, "smoke.npz")
    scripts = make_scripts(matches, TICKS, seed=SEED)
    desyncs = []
    for t in range(TICKS):
        injector.advance(t)
        for m, keys in enumerate(matches):
            for k, key in enumerate(keys):
                if key in host._lanes:
                    host.submit_input(key, k, bytes([scripts[(m, k)][t]]))
        for key, evs in host.tick().items():
            desyncs += [
                (key, e) for e in evs
                if type(e).__name__ == "DesyncDetected"
            ]
        if t == corrupt_ticks[0]:
            host.checkpoint(ckpt)
        clock.advance(FRAME_MS)
    host.device.block_until_ready()
    host._resolve_audits()

    # --- 1. survivors keep serving -----------------------------------
    victim_keys = set(victims)
    survivor_desyncs = [(k, e) for k, e in desyncs if k not in victim_keys]
    if survivor_desyncs:
        fail(f"survivors desynced: {survivor_desyncs[:3]}")
    stalled = [
        key
        for m, keys in enumerate(matches) if m > 1
        for key in keys
        if host._lanes[key].current_frame <= TICKS // 2
    ]
    if stalled:
        fail(f"survivor lanes stalled: {stalled}")

    # --- 2. typed containment ----------------------------------------
    for kind in FAULT_KINDS:
        if injector.fired[kind] < 1:
            fail(f"fault kind {kind!r} never fired: {injector.fired}")
    poisoned = host.take_quarantines()
    if not poisoned:
        fail("no quarantines surfaced")
    for p in poisoned:
        if not isinstance(p, SlotPoisoned):
            fail(f"untyped quarantine {p!r}")
        if not p.forensics or not os.path.exists(p.forensics):
            fail(f"quarantine without a forensics bundle: {p}")
    if not any(p.reason == "sdc_audit" for p in poisoned):
        fail(
            "injected SDC was not caught by the audit lane: "
            f"{[(p.key, p.reason) for p in poisoned]}"
        )
    flipped = {b["key"] for b in injector.bitflips}
    if not flipped & {p.key for p in poisoned}:
        fail("the flipped lane was not the quarantined one")
    try:
        load_device_checkpoint(ckpt)
        fail("corrupted checkpoint loaded without a typed error")
    except CheckpointIncompatible:
        pass

    # --- 3. recompile-clean, jit cache within budget ------------------
    recompiles = san.recompiles[base_recompiles:]
    if recompiles:
        fail(
            "post-warmup recompile under device faults:\n"
            + "\n".join(e.render() for e in recompiles)
        )
    dev = host.device
    cache = sum(fn._cache_size() for fn in dev._budget_fns().values())
    budget = dev.dispatch_bucket_budget()
    if cache > budget:
        fail(f"jit cache {cache} exceeds budget {budget}")

    # --- 4. instruments through both exporters -----------------------
    snap = host.telemetry()
    m = snap["metrics"]
    for name in (
        "ggrs_faults_injected_total",
        "ggrs_slot_quarantines_total",
        "ggrs_sdc_audits_total",
        "ggrs_sdc_mismatches_total",
        "ggrs_degraded_mode_total",
        "ggrs_invariant_trips_total",
    ):
        if name not in m:
            fail(f"{name} missing from the snapshot exporter")
    prom = validate_prometheus(GLOBAL_TELEMETRY.prometheus())
    for name in (
        "ggrs_faults_injected_total",
        "ggrs_slot_quarantines_total",
        "ggrs_sdc_mismatches_total",
    ):
        if name not in prom:
            fail(f"{name} missing from the prometheus exporter")
    if snap["host"]["quarantines"] != len(poisoned):
        fail("host section quarantine count disagrees")

    print(
        f"fault-smoke OK: fired={dict(injector.fired)} "
        f"quarantines={[(str(p.key), p.reason) for p in poisoned]} "
        f"audits={host.audits_sampled} mismatches={host.audit_mismatches} "
        f"cache={cache}/{budget}"
    )


if __name__ == "__main__":
    main()
