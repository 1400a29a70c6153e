"""BENCHMARK.json and the files it names: every configuration, traffic mix,
driver and metric is a file found by name, and a new cell made of existing
files runs with no edit to any harness file."""

import json
import re
import shutil

import pytest

from benchmark import harness
from benchmark.tests import cells

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files_exist():
    names = [c["name"] for c in SPEC["configs"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and "assumed" in cfg
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(workload):
    c = harness.resolve(SPEC, workload)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer and all(m["moves"] in e2e for m in c.per_layer)
    assert hasattr(c.driver, "Cell")


def test_new_cell_from_existing_files_needs_no_harness_edit(tmp_path):
    """A cell that pairs an existing configuration with an existing mix is
    one entry in BENCHMARK.json; it resolves and runs correct."""
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({
        "name": "exgame_4p_w12.serve_wan", "config": "exgame_4p_w12",
        "traffic": "serve_wan", "chips": 1, "why": "test cell",
    })
    for m in spec["end_to_end"]:
        if "workloads" in m and "exgame_2p_4096.serve_wan" in m["workloads"]:
            m["workloads"].append("exgame_4p_w12.serve_wan")
    (tmp_path / "benchmark" / "configs").mkdir(parents=True)
    for c in spec["configs"]:
        shutil.copy(harness.ROOT / c["file"], tmp_path / c["file"])
    before = {p: p.read_bytes() for p in harness.HERE.rglob("*.py")}
    r = cells.run("exgame_4p_w12.serve_wan", spec=spec, root=tmp_path,
                  sizes={"entities": 128, "sessions": 8})
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"session_ticks_per_s", "host_tick_ms_p95",
                                 "setup_s"}
    assert before == {p: p.read_bytes() for p in harness.HERE.rglob("*.py")}


def test_unknown_cell_is_refused():
    with pytest.raises(harness.CellError):
        harness.resolve(SPEC, "no_such.cell")


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    assert harness.main(["--workload", "exgame_2p_4096.synctest_d8",
                         "--seed", "1", "--seconds", "1", "--trace", "0"]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_entries_keep_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for section, keys in KEYS.items():
        for e in SPEC[section]:
            extra = set(e) - keys
            assert keys <= set(e) and extra <= {"workloads"}, (section, e)
            for k in ("why", "layer", "source"):
                if k in e and section != "end_to_end" and section != "per_layer":
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    chips = [w["chips"] for w in SPEC["workloads"]]
    assert set(chips) <= {1, 4} and sum(c == 4 for c in chips) <= max(1, len(chips) // 2)
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) == len(chips)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_traffic_and_configs_name_what_they_assume():
    """Every mix names its source and marks each chosen value `assumed`
    with its reason; every configuration gives a reason for each value it
    assumes."""
    for w in SPEC["workloads"]:
        t = json.loads((harness.HERE / "traffic" / f"{w['traffic']}.json").read_text())
        assert t["source"] and t["assumed"]
        assert all(isinstance(v, str) and v for v in t["assumed"].values())
    for c in SPEC["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert set(cfg["assumed"]) <= set(cfg["assumed_why"])
