"""BENCHMARK.json keeps to the benchmark's contract: names, units and
limits of every field, a reader and data file for everything it names,
and every per-layer metric reported only in cells that report the
end-to-end metric it moves."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTHS = {"d_model", "d_ff", "head_dim", "moe_d_ff", "dense_d_ff",
          "kv_lora_rank", "q_lora_rank", "ssm_state", "ssm_expand",
          "top_k", "window"}


def line_text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(SPEC) == KEYS["top"]
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(line_text(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_entries(section):
    entries = SPEC[section]
    limit = {"configs": 24, "workloads": 24, "end_to_end": 16,
             "per_layer": 128}[section]
    assert 1 <= len(entries) <= limit
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        extra = set(e) - KEYS[section]
        assert set(e) >= KEYS[section] and extra <= (
            {"workloads"} if section in ("end_to_end", "per_layer")
            else set()), e
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert line_text(e[k]), (k, e[k])
        if section in ("end_to_end", "per_layer"):
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
            assert (ROOT / "bench" / "metrics" / f"{e['name']}.py").is_file()
        if section == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace")
            assert 0.01 <= e["bound"] <= 0.25
        if section == "per_layer":
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")


def test_configs_and_cells_have_their_files():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        path = ROOT / c["file"]
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        conf = json.loads(path.read_text())
        assert conf["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key) and key not in WIDTHS
            assert not key.endswith(("_dim", "_rank"))
            assert conf["published"][key] != conf["model"][key]
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        cell = harness.load_cell(w["name"])
        assert cell.traffic["mode"] in harness.MODES
        assert "limits" in cell.params


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_configs_name_a_reference_that_keeps_the_contract(entry):
    """Each configuration names its architecture's reference: a file under
    the benchmark's paths that defines ``init_params``, ``nll_sum``,
    ``model_flops_per_token`` and ``TINY``."""
    conf = json.loads((ROOT / entry["file"]).read_text())
    path = conf["reference"]
    assert PATH.match(path) and not path.startswith("/") and ".." not in path
    assert any(path.startswith(p + "/") for p in SPEC["paths"])
    assert (ROOT / path).is_file()
    arch = harness.load_reference(ROOT / path)
    for fn in ("init_params", "nll_sum", "model_flops_per_token"):
        assert callable(getattr(arch, fn, None)), fn
    assert isinstance(arch.TINY, dict) and arch.TINY
    assert set(arch.TINY) <= set(conf["model"])


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or cell in moved["workloads"], \
                (m["name"], cell)
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    # metrics of one layer name it letter for letter alike
    assert all(len(v) == 1 for v in layers.values()), layers
