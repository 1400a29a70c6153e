"""Static-analysis suite + retrace sanitizer.

Every lint rule gets at least one true-positive fixture (the rule MUST
fire) and one clean fixture (it MUST NOT) — fed through the same Repo/
run_passes entry point the CLI gate uses, so fixture behavior is gate
behavior. Then the dogfood assertion: the repo itself runs with zero
unbaselined findings. The sanitizer half covers the seeded retrace, the
telemetry wiring (ggrs_recompiles_total through both exporters +
flight-recorder events in host.telemetry()), the dispatch-budget
assertion, and a hosted warmup+serve scenario that must stay
recompile-clean under the sanitizer."""

import os

import pytest

from ggrs_tpu.analysis import (
    RULES,
    Repo,
    apply_baseline,
    format_baseline,
    parse_baseline,
    run_passes,
)
from ggrs_tpu.analysis.baseline import BaselineEntry


def rules_fired(files, passes=None):
    findings = run_passes(Repo(files=files), passes)
    for f in findings:
        assert f.rule in RULES, f"unregistered rule id {f.rule}"
    return [f.rule for f in findings], findings


# ----------------------------------------------------------------------
# determinism (DET001..DET004)
# ----------------------------------------------------------------------


def test_det001_wall_clock_fires_and_clean_passes():
    bad = {"ggrs_tpu/tpu/fx.py": (
        "import time\n"
        "def stamp():\n"
        "    return time.time()\n"
    )}
    rules, _ = rules_fired(bad, ["determinism"])
    assert rules == ["DET001"]
    clean = {"ggrs_tpu/tpu/fx.py": (
        "import time\n"
        "def pace():\n"
        "    return time.perf_counter()\n"  # monotonic pacing is host-side
    )}
    assert rules_fired(clean, ["determinism"])[0] == []


def test_det001_out_of_scope_module_not_linted():
    # obs/ timestamps events on purpose; the determinism scope excludes it
    files = {"ggrs_tpu/obs/fx.py": "import time\nT = time.time()\n"}
    assert rules_fired(files, ["determinism"])[0] == []


def test_det002_unseeded_rng():
    bad = {"ggrs_tpu/models/fx.py": (
        "import random\n"
        "import numpy as np\n"
        "def roll():\n"
        "    return random.randint(0, 3) + np.random.rand()\n"
        "def entropy():\n"
        "    return np.random.default_rng()\n"
    )}
    rules, _ = rules_fired(bad, ["determinism"])
    assert rules == ["DET002", "DET002", "DET002"]
    clean = {"ggrs_tpu/models/fx.py": (
        "import random\n"
        "import numpy as np\n"
        "def roll(seed):\n"
        "    rng = random.Random(seed)\n"
        "    g = np.random.default_rng(seed)\n"
        "    return rng.randint(0, 3) + g.uniform()\n"
    )}
    assert rules_fired(clean, ["determinism"])[0] == []


def test_det002_stateful_rng_draft_path():
    # the speculative-bubble-filling draft contract: every draw in a
    # draft script is a counter-based uniform of (seed, frame, player)
    # (tpu/input_model.draft_script, env/opponents.unit_uniform). A
    # draft path that keeps a STATEFUL RNG stream instead — where the
    # k-th draw depends on how many draws preceded it, so a re-draft of
    # the same anchor yields a different script — must be a DET002
    # true positive, not something the lint waves through.
    bad = {"ggrs_tpu/tpu/draftfx.py": (
        "import numpy as np\n"
        "class Drafter:\n"
        "    def __init__(self):\n"
        "        self._rng = np.random.default_rng()\n"
        "    def draft_script(self, depth):\n"
        "        # stateful stream: draw k depends on draws 0..k-1\n"
        "        return [self._rng.random() for _ in range(depth)]\n"
    )}
    rules, findings = rules_fired(bad, ["determinism"])
    assert rules == ["DET002"]
    assert findings[0].path == "ggrs_tpu/tpu/draftfx.py"
    # the shipped shape: counter-based draws keyed on (seed, frame,
    # player) — byte-identical on re-draft, nothing for the lint to say
    clean = {"ggrs_tpu/tpu/draftfx.py": (
        "from ggrs_tpu.env.opponents import unit_uniform\n"
        "def draft_script(seed, anchor, depth, players):\n"
        "    return [unit_uniform(seed, anchor + j, players)\n"
        "            for j in range(depth)]\n"
    )}
    assert rules_fired(clean, ["determinism"])[0] == []


def test_det003_id_hash():
    bad = {"ggrs_tpu/sync_layer.py": (
        "def key(cell):\n"
        "    return id(cell) ^ hash('x')\n"
    )}
    rules, _ = rules_fired(bad, ["determinism"])
    assert rules == ["DET003", "DET003"]
    clean = {"ggrs_tpu/sync_layer.py": (
        "def key(frame, slot):\n"
        "    return (frame, slot)\n"
    )}
    assert rules_fired(clean, ["determinism"])[0] == []


def test_det004_set_iteration():
    bad = {"ggrs_tpu/input_queue.py": (
        "def drain(pending):\n"
        "    out = []\n"
        "    for p in set(pending):\n"
        "        out.append(p)\n"
        "    return out + list({1, 2, 3})\n"
    )}
    rules, _ = rules_fired(bad, ["determinism"])
    assert rules == ["DET004", "DET004"]
    clean = {"ggrs_tpu/input_queue.py": (
        "def drain(pending):\n"
        "    has = 3 in set(pending)\n"  # membership: order-free
        "    return [p for p in sorted(set(pending))], has\n"
    )}
    assert rules_fired(clean, ["determinism"])[0] == []


# ----------------------------------------------------------------------
# trace discipline (TRC001..TRC004)
# ----------------------------------------------------------------------


def test_trc001_host_sync_in_traced_fn():
    bad = {"ggrs_tpu/tpu/fx.py": (
        "import jax\n"
        "import numpy as np\n"
        "def build():\n"
        "    def impl(x):\n"
        "        v = float(x)\n"
        "        h = np.asarray(x)\n"
        "        return x.item() + v\n"
        "    return jax.jit(impl)\n"
    )}
    rules, _ = rules_fired(bad, ["trace_discipline"])
    assert sorted(rules) == ["TRC001", "TRC001", "TRC001"]
    clean = {"ggrs_tpu/tpu/fx.py": (
        "import jax\n"
        "import numpy as np\n"
        "from ggrs_tpu.types import InputStatus\n"
        "def build():\n"
        "    def impl(x):\n"
        "        n = int(x.shape[0])\n"      # shape read: static
        "        k = int(InputStatus.CONFIRMED)\n"  # global enum: concrete
        "        return x * n + k\n"
        "    host = np.asarray([1, 2])\n"    # host scope: not traced
        "    return jax.jit(impl), host\n"
    )}
    assert rules_fired(clean, ["trace_discipline"])[0] == []


def test_trc002_branch_on_traced_arg_and_static_argnums():
    bad = {"ggrs_tpu/tpu/fx.py": (
        "import jax\n"
        "def build():\n"
        "    def impl(x):\n"
        "        if x > 0:\n"
        "            return x\n"
        "        return -x\n"
        "    return jax.jit(impl)\n"
    )}
    assert rules_fired(bad, ["trace_discipline"])[0] == ["TRC002"]
    # same branch, but the argument is a static jit key -> clean
    clean = {"ggrs_tpu/tpu/fx.py": (
        "import jax\n"
        "def build():\n"
        "    def impl(x, mode):\n"
        "        if mode > 0:\n"
        "            return x\n"
        "        if mode is None:\n"  # sentinel: structural, fine
        "            return x\n"
        "        return -x\n"
        "    return jax.jit(impl, static_argnums=(1,))\n"
    )}
    assert rules_fired(clean, ["trace_discipline"])[0] == []


def test_trc002_bound_method_static_argnums_skip_self():
    # static_argnums index the call-time signature of the BOUND method
    clean = {"ggrs_tpu/tpu/fx.py": (
        "import jax\n"
        "class Core:\n"
        "    def _impl(self, ring, state, nslots):\n"
        "        if nslots > 4:\n"
        "            return ring\n"
        "        return state\n"
        "    def __init__(self):\n"
        "        self.fn = jax.jit(self._impl, static_argnums=(2,))\n"
    )}
    assert rules_fired(clean, ["trace_discipline"])[0] == []


def test_trc003_closure_mutation():
    bad = {"ggrs_tpu/tpu/fx.py": (
        "import jax\n"
        "log = []\n"
        "class Core:\n"
        "    def _impl(self, x):\n"
        "        log.append(1)\n"
        "        self.cache = x\n"
        "        return x\n"
        "    def build(self):\n"
        "        return jax.jit(self._impl)\n"
    )}
    rules, _ = rules_fired(bad, ["trace_discipline"])
    assert sorted(rules) == ["TRC003", "TRC003"]
    # pallas kernels mutate Ref cells by design: not a violation
    clean = {"ggrs_tpu/tpu/fx.py": (
        "import jax\n"
        "from jax.experimental import pallas as pl\n"
        "def build(spec):\n"
        "    def kernel(x_ref, o_ref):\n"
        "        def tick(i):\n"
        "            o_ref[i] = x_ref[i] * 2\n"
        "        jax.lax.fori_loop(0, 4, lambda i, _: tick(i), None)\n"
        "    return pl.pallas_call(kernel, out_shape=spec)\n"
    )}
    assert rules_fired(clean, ["trace_discipline"])[0] == []


def test_trc003_subscript_store_through_self_attr():
    bad = {"ggrs_tpu/tpu/fx.py": (
        "import jax\n"
        "class Core:\n"
        "    def _impl(self, x):\n"
        "        self.buf[0] = x\n"
        "        return x\n"
        "    def build(self):\n"
        "        return jax.jit(self._impl)\n"
    )}
    rules, findings = rules_fired(bad, ["trace_discipline"])
    assert rules == ["TRC003"]
    assert "self.buf" in findings[0].message


def test_trc004_jit_cache_per_call():
    bad = {"ggrs_tpu/serve/fx.py": (
        "import jax\n"
        "def serve(xs):\n"
        "    outs = []\n"
        "    for x in xs:\n"
        "        outs.append(jax.jit(lambda a: a + 1)(x))\n"
        "    y = jax.jit(lambda a: a * 2)(xs[0])\n"
        "    return outs, y\n"
    )}
    rules, _ = rules_fired(bad, ["trace_discipline"])
    assert rules == ["TRC004", "TRC004"]
    clean = {"ggrs_tpu/serve/fx.py": (
        "import jax\n"
        "STEP = jax.jit(lambda a: a + 1)\n"  # module scope: one cache
        "def serve(xs):\n"
        "    return [STEP(x) for x in xs]\n"
    )}
    assert rules_fired(clean, ["trace_discipline"])[0] == []


# ----------------------------------------------------------------------
# fence discipline (FEN001)
# ----------------------------------------------------------------------

_FENCE_BAD = """
class TpuRollbackBackend:
    def __init__(self):
        self._inflight = []
    def _note_inflight(self, h):
        self._inflight.append(h)
    def sneaky_reset(self):
        self._inflight.clear()
    def sneaky_swap(self):
        self._multi_active = None
"""

_FENCE_CLEAN = """
class TpuRollbackBackend:
    def __init__(self):
        self._inflight = []
        self.beam_hits = 0
    def _note_inflight(self, h):
        self._inflight.append(h)
    def flush(self):
        self._inflight.clear()
    def anywhere(self):
        self.beam_hits += 1          # unprotected attr: free
        n = len(self._inflight)      # reads: always fine
        return n
"""


def test_fen001_fires_outside_entry_points_only():
    rules, findings = rules_fired(
        {"ggrs_tpu/tpu/backend.py": _FENCE_BAD}, ["fence"]
    )
    assert rules == ["FEN001", "FEN001"]
    assert {f.symbol for f in findings} == {
        "TpuRollbackBackend.sneaky_reset",
        "TpuRollbackBackend.sneaky_swap",
    }
    assert rules_fired(
        {"ggrs_tpu/tpu/backend.py": _FENCE_CLEAN}, ["fence"]
    )[0] == []


def test_fen001_host_never_touches_device_internals():
    bad = {"ggrs_tpu/serve/host.py": (
        "class SessionHost:\n"
        "    def hack(self):\n"
        "        self.device._inflight.clear()\n"
        "        self.device.rows_this_tick = 0\n"
        "    def hack_tuple(self):\n"
        "        # the codebase's canonical write form for the stacked\n"
        "        # worlds must not slip through as tuple unpacking\n"
        "        self.device.rings, self.device.states, x, y = restore()\n"
    )}
    rules, _ = rules_fired(bad, ["fence"])
    assert rules == ["FEN001", "FEN001", "FEN001", "FEN001"]
    clean = {"ggrs_tpu/serve/host.py": (
        "class SessionHost:\n"
        "    def ok(self):\n"
        "        self.device.begin_tick()\n"
    )}
    assert rules_fired(clean, ["fence"])[0] == []


# ----------------------------------------------------------------------
# wire contract (WIRE001..WIRE004)
# ----------------------------------------------------------------------

_MSG_PY_OK = (
    "import struct\n"
    "MSG_SYNC_REQUEST = 0\n"
    "MSG_SYNC_REPLY = 1\n"
    "_HEADER = struct.Struct('<HB')\n"
)
_EP_CPP_OK = (
    "constexpr uint8_t MSG_SYNC_REQUEST = 0;\n"
    "constexpr uint8_t MSG_SYNC_REPLY = 1;\n"
)


def test_wire001_msg_code_drift():
    bad = {
        "ggrs_tpu/network/messages.py": _MSG_PY_OK,
        "native/endpoint.cpp": (
            "constexpr uint8_t MSG_SYNC_REQUEST = 0;\n"
            "constexpr uint8_t MSG_SYNC_REPLY = 2;\n"  # drifted
        ),
    }
    rules, _ = rules_fired(bad, ["wire_contract"])
    assert "WIRE001" in rules
    clean = {
        "ggrs_tpu/network/messages.py": _MSG_PY_OK,
        "native/endpoint.cpp": _EP_CPP_OK,
    }
    assert rules_fired(clean, ["wire_contract"])[0] == []


def test_wire002_ctypes_struct_drift():
    h = (
        "struct ggrs_ep_stats {\n"
        "  int32_t send_queue_len;\n"
        "  uint32_t ping_ms;\n"
        "};\n"
    )
    bad = {
        "ggrs_tpu/native/endpoint.py": (
            "import ctypes\n"
            "class _Stats(ctypes.Structure):\n"
            "    _fields_ = [\n"
            "        ('send_queue_len', ctypes.c_int32),\n"
            "        ('ping_ms', ctypes.c_int32),\n"  # wrong sign/type
            "    ]\n"
        ),
        "native/ggrs_native.h": h,
    }
    rules, _ = rules_fired(bad, ["wire_contract"])
    assert rules == ["WIRE002"]
    clean = {
        "ggrs_tpu/native/endpoint.py": (
            "import ctypes\n"
            "class _Stats(ctypes.Structure):\n"
            "    _fields_ = [\n"
            "        ('send_queue_len', ctypes.c_int32),\n"
            "        ('ping_ms', ctypes.c_uint32),\n"
            "    ]\n"
        ),
        "native/ggrs_native.h": h,
    }
    assert rules_fired(clean, ["wire_contract"])[0] == []


def test_wire003_buffer_bound_drift():
    bad = {
        "ggrs_tpu/network/sockets.py": (
            "RECV_BUFFER_SIZE = 65536\n"
            "MAX_DATAGRAM_SIZE = min(RECV_BUFFER_SIZE, 65507)\n"
        ),
        "ggrs_tpu/native/session.py": "_WIRE_BUF_CAP = 4096\n",
    }
    rules, _ = rules_fired(bad, ["wire_contract"])
    assert "WIRE003" in rules
    clean = {
        "ggrs_tpu/network/sockets.py": (
            "RECV_BUFFER_SIZE = 65536\n"
            "MAX_DATAGRAM_SIZE = min(RECV_BUFFER_SIZE, 65507)\n"
        ),
        "ggrs_tpu/native/session.py": (
            "from ..network.sockets import RECV_BUFFER_SIZE\n"
            "_WIRE_BUF_CAP = RECV_BUFFER_SIZE\n"
        ),
    }
    assert rules_fired(clean, ["wire_contract"])[0] == []


def test_wire004_shared_constant_drift():
    bad = {
        "ggrs_tpu/network/protocol.py": "MAX_PAYLOAD = 467\n",
        "native/endpoint.cpp": "constexpr size_t MAX_PAYLOAD = 400;\n",
    }
    rules, _ = rules_fired(bad, ["wire_contract"])
    assert rules == ["WIRE004"]
    clean = {
        "ggrs_tpu/network/protocol.py": "MAX_PAYLOAD = 467\n",
        "native/endpoint.cpp": "constexpr size_t MAX_PAYLOAD = 467;\n",
    }
    assert rules_fired(clean, ["wire_contract"])[0] == []


# ----------------------------------------------------------------------
# baseline mechanics
# ----------------------------------------------------------------------


def test_baseline_roundtrip_and_ratchet():
    entries = [
        BaselineEntry(
            rule="DET001", path="ggrs_tpu/tpu/fx.py", symbol="stamp",
            justification='bench-only "timer", quoted + escaped \\ path',
            count=2,
        )
    ]
    text = format_baseline(entries, header="test header")
    parsed = parse_baseline(text)
    assert parsed == entries

    files = {"ggrs_tpu/tpu/fx.py": (
        "import time\n"
        "def stamp():\n"
        "    return time.time() + time.time() + time.time()\n"
    )}
    findings = run_passes(Repo(files=files), ["determinism"])
    assert len(findings) == 3
    fresh, suppressed, stale = apply_baseline(findings, parsed)
    # count=2 suppresses two occurrences, the third stays fresh
    assert len(suppressed) == 2 and len(fresh) == 1 and stale == []

    # a stale entry is reported once nothing matches
    fresh2, _, stale2 = apply_baseline([], parsed)
    assert fresh2 == [] and len(stale2) == 1


def test_baseline_rejects_malformed():
    with pytest.raises(Exception):
        parse_baseline("rule = \"DET001\"\n")  # key outside a table


def test_baseline_duplicate_keys_stack_not_shadow():
    # two [[finding]] entries for one key: budgets add up in file order
    entries = [
        BaselineEntry(rule="DET001", path="ggrs_tpu/tpu/fx.py",
                      symbol="stamp", justification="first"),
        BaselineEntry(rule="DET001", path="ggrs_tpu/tpu/fx.py",
                      symbol="stamp", justification="second"),
    ]
    files = {"ggrs_tpu/tpu/fx.py": (
        "import time\n"
        "def stamp():\n"
        "    return time.time() + time.time()\n"
    )}
    findings = run_passes(Repo(files=files), ["determinism"])
    assert len(findings) == 2
    fresh, suppressed, stale = apply_baseline(findings, entries)
    assert fresh == [] and len(suppressed) == 2 and stale == []


def test_baseline_trailing_backslash_roundtrips():
    entries = [BaselineEntry(
        rule="DET001", path="p.py", symbol="f",
        justification="windows path C:\\tmp\\",  # ends in a backslash
    )]
    assert parse_baseline(format_baseline(entries)) == entries


# ----------------------------------------------------------------------
# dogfood: the repo itself holds the gate
# ----------------------------------------------------------------------


def test_repo_runs_clean_against_baseline():
    repo = Repo.from_here()
    assert repo.root and os.path.isdir(os.path.join(repo.root, "ggrs_tpu"))
    findings = run_passes(repo)
    baseline_path = os.path.join(
        repo.root, "ggrs_tpu", "analysis", "baseline.toml"
    )
    entries = []
    if os.path.isfile(baseline_path):
        with open(baseline_path) as f:
            entries = parse_baseline(f.read())
    for e in entries:  # every audited entry must carry a real reason
        assert e.justification and "TODO" not in e.justification
    fresh, _, _ = apply_baseline(findings, entries)
    assert fresh == [], "unbaselined findings:\n" + "\n".join(
        f.render() for f in fresh
    )


def test_cli_exits_nonzero_on_findings(tmp_path):
    import subprocess
    import sys

    root = tmp_path / "repo"
    (root / "ggrs_tpu" / "tpu").mkdir(parents=True)
    (root / "ggrs_tpu" / "tpu" / "bad.py").write_text(
        "import time\nT = time.time()\n"
    )
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo_root)
    proc = subprocess.run(
        [sys.executable, "-m", "ggrs_tpu.analysis", "--root", str(root),
         "--no-baseline"],
        capture_output=True, text=True, env=env,
    )
    assert proc.returncode == 1
    assert "DET001" in proc.stdout

    proc2 = subprocess.run(
        [sys.executable, "-m", "ggrs_tpu.analysis", "--root", str(root),
         "--passes", "fence"],
        capture_output=True, text=True, env=env,
    )
    assert proc2.returncode == 0


# ----------------------------------------------------------------------
# retrace sanitizer
# ----------------------------------------------------------------------


@pytest.fixture
def sanitizer():
    from ggrs_tpu.analysis.sanitize import (
        install_sanitizer,
        uninstall_sanitizer,
    )

    san = install_sanitizer()
    san.reset()
    yield san
    san.reset()
    uninstall_sanitizer()


def test_sanitizer_catches_seeded_retrace(sanitizer):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return x + 1

    step(jnp.ones(3))
    sanitizer.freeze("test warmup")
    for n in (4, 5, 6):
        step(jnp.ones(n))
    assert len(sanitizer.recompiles) == 3
    assert all(
        "test_analysis.py" in e.provenance() for e in sanitizer.recompiles
    )
    report = sanitizer.report()
    assert "RECOMPILE" in report and "test_analysis.py" in report


def test_sanitizer_telemetry_counters_and_events(sanitizer):
    import jax
    import jax.numpy as jnp

    from ggrs_tpu.obs import GLOBAL_TELEMETRY

    GLOBAL_TELEMETRY.enabled = True
    try:
        GLOBAL_TELEMETRY.registry.reset()
        GLOBAL_TELEMETRY.recorder.clear()

        @jax.jit
        def step(x):
            return x * 2

        step(jnp.ones(2))
        sanitizer.freeze("telemetry test")
        step(jnp.ones(5))  # one recompile

        reg = GLOBAL_TELEMETRY.registry
        assert reg.get("ggrs_program_compiles_total").value == 2
        assert reg.get("ggrs_recompiles_total").value == 1
        prom = GLOBAL_TELEMETRY.prometheus()
        assert "ggrs_recompiles_total 1" in prom
        snap = GLOBAL_TELEMETRY.snapshot()
        assert snap["metrics"]["ggrs_recompiles_total"]["values"][""] == 1
        kinds = [e["kind"] for e in snap["events"]]
        assert "program_compile" in kinds
        assert "unexpected_recompile" in kinds
        recomp = [
            e for e in snap["events"] if e["kind"] == "unexpected_recompile"
        ][0]
        assert "test_analysis.py" in recomp["provenance"]
    finally:
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


def test_sanitizer_dispatch_budget_raises(sanitizer):
    import jax
    import jax.numpy as jnp

    from ggrs_tpu.errors import RetraceBudgetExceeded

    @jax.jit
    def prog(x):
        return x.sum()

    for n in (2, 3, 4):  # 3 cached programs
        prog(jnp.ones(n))
    sanitizer.check_dispatch_budget({"prog": prog}, budget=3)  # at bound: ok
    with pytest.raises(RetraceBudgetExceeded) as exc:
        sanitizer.check_dispatch_budget({"prog": prog}, budget=2)
    assert "dispatch-bucket budget" in str(exc.value)
    assert "test_analysis.py" in str(exc.value)


def test_second_warmup_thaws_then_refreezes(sanitizer):
    """A later backend's warmup is legitimate compilation: it must lift a
    standing freeze for its duration instead of reporting its own grid
    compile as phantom mid-serve recompiles."""
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.tpu import TpuRollbackBackend

    sanitizer.freeze("earlier backend's warmup")
    backend = TpuRollbackBackend(
        ExGame(num_players=2, num_entities=8), max_prediction=2,
        num_players=2,
    )
    backend.warmup()
    assert sanitizer.recompiles == [], sanitizer.report()
    assert len(sanitizer.compiles) > 0
    assert sanitizer.freeze_label == "TpuRollbackBackend.warmup"


def test_warmup_refreezes_even_when_it_raises(sanitizer):
    """A failed warmup must not leave the sanitizer thawed process-wide:
    recompile detection stays armed for the cores that ARE serving."""
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.tpu import TpuRollbackBackend

    backend = TpuRollbackBackend(
        ExGame(num_players=2, num_entities=8), max_prediction=2,
        num_players=2,
    )
    sanitizer.freeze("pre-existing freeze")
    backend._warmup_impl = lambda: (_ for _ in ()).throw(
        RuntimeError("device fell over mid-warmup")
    )
    with pytest.raises(RuntimeError):
        backend.warmup()
    assert sanitizer.frozen_at is not None
    assert sanitizer.freeze_label == "TpuRollbackBackend.warmup"


def test_hosted_serve_recompile_clean_under_sanitizer(sanitizer):
    """The acceptance gate's positive control: warmup compiles the whole
    megabatch grid, then an actual hosted serve (solo P2P lanes ticking
    through the megabatch scheduler) must not compile ANYTHING — and the
    in-dispatch budget assertion must hold throughout."""
    from ggrs_tpu import PlayerType, SessionBuilder
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.network.sockets import InMemoryNetwork
    from ggrs_tpu.obs import GLOBAL_TELEMETRY
    from ggrs_tpu.serve import SessionHost
    from ggrs_tpu.utils.clock import FakeClock

    GLOBAL_TELEMETRY.enabled = True
    GLOBAL_TELEMETRY.registry.reset()
    clock = FakeClock()
    net = InMemoryNetwork(clock)
    host = SessionHost(
        ExGame(num_players=2, num_entities=8),
        max_prediction=4,
        num_players=2,
        max_sessions=4,
        clock=clock,
        warmup=True,  # compiles the grid, then freezes the sanitizer
    )
    assert sanitizer.frozen_at is not None
    assert sanitizer.freeze_label == "MultiSessionDeviceCore.warmup"
    assert len(sanitizer.compiles) > 0

    keys = []
    for i in range(3):
        b = (
            SessionBuilder(input_size=1)
            .with_num_players(2)
            .with_max_prediction_window(4)
        )
        for h in range(2):
            b = b.add_player(PlayerType.local(), h)
        session = b.start_p2p_session(net.socket(("solo", i)))
        keys.append(host.attach(session))
    for t in range(24):
        for i, key in enumerate(keys):
            for h in range(2):
                host.submit_input(key, h, bytes([(t * 3 + h + i) % 16]))
        host.tick()
        clock.advance(16)
    try:
        host.device.block_until_ready()
        assert host.device.megabatches > 0
        assert sanitizer.recompiles == [], (
            "hosted serve recompiled mid-serve:\n" + sanitizer.report()
        )
        # the counter rides host.telemetry() and both exporters, at zero
        snap = host.telemetry()
        assert snap["metrics"]["ggrs_recompiles_total"]["values"][""] == 0
        assert snap["metrics"]["ggrs_program_compiles_total"]["values"][""] > 0
        assert "ggrs_recompiles_total 0" in GLOBAL_TELEMETRY.prometheus()
    finally:
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


# ----------------------------------------------------------------------
# alloc (ALLOC001..ALLOC004) — fixtures opt in via __ggrs_hot__
# ----------------------------------------------------------------------


def test_alloc001_per_iteration_container_fires_and_scratch_clean():
    bad = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('Host.tick',)\n"
        "class Host:\n"
        "    def tick(self, lanes):\n"
        "        for lane in lanes:\n"
        "            rows = [lane.row]\n"
        "            self.emit(rows)\n"
        "    def emit(self, rows):\n"
        "        pass\n"
    )}
    rules, _ = rules_fired(bad, ["alloc"])
    assert rules == ["ALLOC001"]
    clean = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('Host.tick',)\n"
        "class Host:\n"
        "    def __init__(self):\n"
        "        self._scratch = []\n"
        "    def tick(self, lanes):\n"
        "        scratch = self._scratch\n"
        "        scratch.clear()\n"
        "        for lane in lanes:\n"
        "            scratch.append(lane.row)\n"
        "        self.emit(scratch)\n"
        "    def emit(self, rows):\n"
        "        pass\n"
    )}
    assert rules_fired(clean, ["alloc"])[0] == []


def test_alloc001_reaches_through_callees():
    # the allocation sits two calls below the declared hot entry
    bad = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('Host.tick',)\n"
        "class Host:\n"
        "    def tick(self, lanes):\n"
        "        self._pump(lanes)\n"
        "    def _pump(self, lanes):\n"
        "        self._drain(lanes)\n"
        "    def _drain(self, lanes):\n"
        "        for lane in lanes:\n"
        "            lane.out = {'k': lane.row}\n"
    )}
    rules, found = rules_fired(bad, ["alloc"])
    assert rules == ["ALLOC001"]
    assert found[0].symbol == "Host._drain"


def test_alloc001_cold_contexts_do_not_fire():
    # lazy-init guard, except handler and raise argument are cold by
    # contract: they allocate only off the steady state
    clean = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('Host.tick',)\n"
        "class Host:\n"
        "    def tick(self, lanes):\n"
        "        for lane in lanes:\n"
        "            q = self.groups.get(lane.key)\n"
        "            if q is None:\n"
        "                q = self.groups[lane.key] = []\n"
        "            q.append(lane.row)\n"
        "            try:\n"
        "                lane.step()\n"
        "            except RuntimeError:\n"
        "                self.failed = [lane.key]\n"
    )}
    assert rules_fired(clean, ["alloc"])[0] == []


def test_alloc002_per_call_closures():
    bad = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('Host.tick',)\n"
        "class Host:\n"
        "    def tick(self, rows):\n"
        "        rows.sort(key=lambda r: r.slot)\n"
    )}
    rules, _ = rules_fired(bad, ["alloc"])
    assert rules == ["ALLOC002"]
    clean = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('Host.tick',)\n"
        "class Host:\n"
        "    def tick(self, rows):\n"
        "        rows.sort(key=self._slot_key)\n"
        "    def _slot_key(self, r):\n"
        "        return r.slot\n"
    )}
    assert rules_fired(clean, ["alloc"])[0] == []


def test_alloc003_string_building_vs_pooled_bytes():
    bad = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('pump',)\n"
        "def pump(rows):\n"
        "    return f'batch of {len(rows)}'\n"
    )}
    rules, _ = rules_fired(bad, ["alloc"])
    assert rules == ["ALLOC003"]
    # b''.join is the sanctioned pooled byte-staging flush, and strings
    # on the raise path are cold
    clean = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('pump',)\n"
        "def pump(chunks, n):\n"
        "    if n < 0:\n"
        "        raise ValueError(f'bad row count {n}')\n"
        "    return b''.join(chunks)\n"
    )}
    assert rules_fired(clean, ["alloc"])[0] == []


def test_alloc004_packing_and_sorted_in_loop():
    bad = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('Host.tick',)\n"
        "class Host:\n"
        "    def tick(self, *rows, **opts):\n"
        "        for group in self.groups:\n"
        "            for e in sorted(group):\n"
        "                e.go()\n"
    )}
    rules, _ = rules_fired(bad, ["alloc"])
    assert sorted(rules) == ["ALLOC004", "ALLOC004"]
    clean = {"ggrs_tpu/serve/fx.py": (
        "__ggrs_hot__ = ('Host.tick',)\n"
        "class Host:\n"
        "    def tick(self, rows, opts):\n"
        "        batch = sorted(rows)\n"
        "        for e in batch:\n"
        "            e.go()\n"
    )}
    assert rules_fired(clean, ["alloc"])[0] == []


def test_alloc_unseeded_module_not_linted():
    # no __ggrs_hot__ and not in the entry table: nothing is hot
    files = {"ggrs_tpu/serve/fx.py": (
        "def helper(rows):\n"
        "    for r in rows:\n"
        "        out = [r]\n"
    )}
    assert rules_fired(files, ["alloc"])[0] == []


# ----------------------------------------------------------------------
# exceptions (EXC001..EXC002)
# ----------------------------------------------------------------------


def test_exc001_untyped_raise_fires_and_bridge_clean():
    bad = {"ggrs_tpu/tpu/fx.py": (
        "def f(n):\n"
        "    raise ValueError('bad: %d' % n)\n"
    )}
    rules, _ = rules_fired(bad, ["exceptions"])
    assert rules == ["EXC001"]
    # the bridge hierarchy resolves across files by the repo-wide
    # class fixpoint: FxError IS a GGRSError even though the raise
    # site's module never mentions GGRSError
    clean = {
        "ggrs_tpu/tpu/fx_err.py": (
            "class FxError(GGRSError, ValueError):\n"
            "    pass\n"
        ),
        "ggrs_tpu/tpu/fx.py": (
            "def f(n):\n"
            "    raise FxError('bad row count')\n"
        ),
    }
    assert rules_fired(clean, ["exceptions"])[0] == []


def test_exc001_reraise_idioms_are_clean():
    clean = {"ggrs_tpu/tpu/fx.py": (
        "class FxError(GGRSError, ValueError):\n"
        "    pass\n"
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except FxError as e:\n"
        "        note(e)\n"
        "        raise\n"
        "def h():\n"
        "    try:\n"
        "        g()\n"
        "    except FxError as e:\n"
        "        raise e.with_traceback(None)\n"
        "def k():\n"
        "    err = FxError('wedged')\n"
        "    note(err)\n"
        "    raise err\n"
    )}
    assert rules_fired(clean, ["exceptions"])[0] == []


def test_exc001_dynamic_expression_fires():
    bad = {"ggrs_tpu/tpu/fx.py": (
        "def f(bag):\n"
        "    raise bag[0]\n"
    )}
    rules, found = rules_fired(bad, ["exceptions"])
    assert rules == ["EXC001"]
    assert "dynamic expression" in found[0].message


def test_exc002_swallowing_broad_except():
    bad = {"ggrs_tpu/tpu/fx.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )}
    rules, _ = rules_fired(bad, ["exceptions"])
    assert rules == ["EXC002"]
    # recording a flight event, or re-raising, redeems the broad catch
    clean = {"ggrs_tpu/tpu/fx.py": (
        "def f(tel):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as exc:\n"
        "        tel.record('fx_failed', error=str(exc))\n"
        "def h():\n"
        "    try:\n"
        "        g()\n"
        "    except BaseException:\n"
        "        raise\n"
    )}
    assert rules_fired(clean, ["exceptions"])[0] == []


def test_cli_json_records(tmp_path):
    import json
    import subprocess
    import sys

    root = tmp_path / "repo"
    (root / "ggrs_tpu" / "tpu").mkdir(parents=True)
    (root / "ggrs_tpu" / "tpu" / "bad.py").write_text(
        "import time\ndef f():\n    raise ValueError(time.time())\n"
    )
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo_root)
    proc = subprocess.run(
        [sys.executable, "-m", "ggrs_tpu.analysis", "--root", str(root),
         "--no-baseline", "--json"],
        capture_output=True, text=True, env=env,
    )
    assert proc.returncode == 1  # exit codes unchanged by --json
    recs = json.loads(proc.stdout)
    assert recs, "expected findings as JSON records"
    for rec in recs:
        assert set(rec) == {"rule", "path", "line", "symbol", "message"}
        assert isinstance(rec["line"], int)
    assert {r["rule"] for r in recs} == {"DET001", "EXC001"}


# ----------------------------------------------------------------------
# allocation sanitizer
# ----------------------------------------------------------------------


@pytest.fixture
def alloc_sanitizer_cleanup():
    from ggrs_tpu.analysis.sanitize import thaw_allocations
    from ggrs_tpu.obs import GLOBAL_TELEMETRY

    GLOBAL_TELEMETRY.enabled = True
    GLOBAL_TELEMETRY.registry.reset()
    GLOBAL_TELEMETRY.recorder.clear()
    yield
    thaw_allocations()
    GLOBAL_TELEMETRY.enabled = False
    GLOBAL_TELEMETRY.reset()


def test_alloc_sanitizer_seeded_regression_trips(alloc_sanitizer_cleanup):
    from ggrs_tpu.analysis.sanitize import (
        active_alloc_sanitizer,
        freeze_allocations,
    )
    from ggrs_tpu.obs import GLOBAL_TELEMETRY

    san = freeze_allocations(budget_blocks=256, label="seeded test")
    assert active_alloc_sanitizer() is san

    for _ in range(20):  # healthy ticks: transient churn only
        scratch = [0] * 8
        scratch.clear()
        san.note_tick()
    assert san.trips == [], san.report()

    hoard = []  # the seeded regression: retained growth every tick
    for _ in range(3):
        hoard.extend(object() for _ in range(5000))
        san.note_tick()
    assert len(san.trips) >= 1, san.report()
    ev = san.trips[0]
    assert ev.blocks > 256 and ev.budget == 256
    assert "test_analysis.py" in ev.provenance()  # tracemalloc names us

    reg = GLOBAL_TELEMETRY.registry
    assert reg.get("ggrs_alloc_budget_trips_total").value >= 1
    hist = reg.get("ggrs_alloc_per_tick").snapshot()["values"][""]
    assert hist["count"] == 23
    snap = GLOBAL_TELEMETRY.snapshot()
    trip_events = [
        e for e in snap["events"] if e["kind"] == "alloc_budget_trip"
    ]
    assert trip_events and "test_analysis.py" in trip_events[0]["provenance"]
    prom = GLOBAL_TELEMETRY.prometheus()
    assert "ggrs_alloc_budget_trips_total" in prom
    assert "ggrs_alloc_per_tick_count" in prom


def test_alloc_sanitizer_thaw_disarms(alloc_sanitizer_cleanup):
    from ggrs_tpu.analysis.sanitize import (
        active_alloc_sanitizer,
        freeze_allocations,
        thaw_allocations,
    )

    san = freeze_allocations(budget_blocks=1, label="thaw test")
    thaw_allocations()
    assert active_alloc_sanitizer() is None
    keep = [object() for _ in range(4096)]
    san.note_tick()  # no-op while thawed
    assert san.trips == [] and keep


def test_alloc_sanitizer_healthy_hosted_serve_silent(alloc_sanitizer_cleanup):
    """The acceptance gate's positive control: a hosted steady-state
    serve, ticked through SessionHost.tick (which carries the
    note_tick probe), must stay under the DEFAULT budget — the tick
    path's zero-steady-state-allocation claim, asserted at runtime."""
    from ggrs_tpu import PlayerType, SessionBuilder
    from ggrs_tpu.analysis.sanitize import freeze_allocations
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.network.sockets import InMemoryNetwork
    from ggrs_tpu.serve import SessionHost
    from ggrs_tpu.utils.clock import FakeClock

    clock = FakeClock()
    net = InMemoryNetwork(clock)
    host = SessionHost(
        ExGame(num_players=2, num_entities=8),
        max_prediction=4,
        num_players=2,
        max_sessions=4,
        clock=clock,
        warmup=True,
    )
    keys = []
    for i in range(3):
        b = (
            SessionBuilder(input_size=1)
            .with_num_players(2)
            .with_max_prediction_window(4)
        )
        for h in range(2):
            b = b.add_player(PlayerType.local(), h)
        session = b.start_p2p_session(net.socket(("solo", i)))
        keys.append(host.attach(session))

    def drive(ticks, base):
        for t in range(ticks):
            for i, key in enumerate(keys):
                for h in range(2):
                    host.submit_input(
                        key, h, bytes([(base + t * 3 + h + i) % 16])
                    )
            host.tick()
            clock.advance(16)

    drive(8, 0)  # warm: caches, pools and lazy slots fill here
    san = freeze_allocations(label="hosted steady state")
    drive(24, 8)
    host.device.block_until_ready()
    assert san.ticks_seen == 24
    assert san.trips == [], (
        "steady-state host tick blew the allocation budget:\n"
        + san.report()
    )


# ----------------------------------------------------------------------
# transfer guard
# ----------------------------------------------------------------------


def test_transfer_guard_trips_on_planted_sync(sanitizer):
    import jax.numpy as jnp

    from ggrs_tpu.analysis.sanitize import transfer_guard_scope
    from ggrs_tpu.errors import GGRSError, ImplicitHostTransfer
    from ggrs_tpu.obs import GLOBAL_TELEMETRY

    GLOBAL_TELEMETRY.enabled = True
    GLOBAL_TELEMETRY.registry.reset()
    GLOBAL_TELEMETRY.recorder.clear()
    try:
        x = jnp.arange(4.0)
        assert float(x.sum()) == 6.0  # warm, unguarded
        sanitizer.freeze("transfer test")
        with pytest.raises(ImplicitHostTransfer) as ei:
            with transfer_guard_scope("resident drive"):
                float(x.sum())  # the planted implicit sync
        assert isinstance(ei.value, GGRSError)  # fleet isolation routes it
        assert "resident drive" in str(ei.value)
        assert "test_analysis.py" in str(ei.value)

        with pytest.raises(ImplicitHostTransfer):
            with transfer_guard_scope("dispatch"):
                x.sum().item()

        snap = GLOBAL_TELEMETRY.snapshot()
        kinds = [e["kind"] for e in snap["events"]]
        assert kinds.count("implicit_host_transfer") == 2
        reg = GLOBAL_TELEMETRY.registry
        assert reg.get("ggrs_transfer_guard_trips_total").value == 2
        # both patches restored once the scope closed
        assert float(x.sum()) == 6.0
        assert x.sum().item() == 6.0
    finally:
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


def test_transfer_guard_inert_unfrozen_and_uninstalled(sanitizer):
    import jax.numpy as jnp

    from ggrs_tpu.analysis.sanitize import transfer_guard_scope

    x = jnp.ones(3)
    # installed but NOT frozen: warmup may read buffers freely
    assert sanitizer.frozen_at is None
    with transfer_guard_scope("dispatch"):
        assert float(x.sum()) == 3.0

    # frozen: host reads OUTSIDE the guarded region stay legal (the
    # drain pass's pooled readback runs outside the scope)
    sanitizer.freeze("inert test")
    assert float(x.sum()) == 3.0
