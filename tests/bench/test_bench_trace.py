"""The reduction from a profiler trace to busy time, op time and idle gaps
attributed to host spans: on hand-made events, and on a small trace
recorded on a TPU v5e and committed under bench/testdata."""
import importlib
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, peaks, tracefile  # noqa: E402
from bench import reference as dense  # noqa: E402

RECORDED = sorted((ROOT / "bench" / "testdata").glob("*.json.gz"))
CONFIG = ROOT / "bench" / "configs" / "danube1.8b-pp8vocab-staged-async.json"
snapshot_roofline = importlib.import_module("bench.metrics.snapshot_roofline")


def test_union_gaps_and_attribution():
    events = {
        "device": [["%fusion.1 = f32[8] fusion()", 100, 200],
                   ["%fusion.2 = f32[8] fusion()", 320, 30],
                   ["%snapshot.1 = s32[1,8,128] custom-call(u32[1,8,128])",
                    600, 100],
                   ["%fusion.1 = f32[8] fusion()", 1200, 50]],
        "host": [["bench.window", 0, 1000],
                 ["bench.train_run", 400, 500],
                 ["bench.restart", 450, 300],
                 ["bench.write_version", 350, 900]],
    }
    r = tracefile.reduce_events(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    # one op lies past the window's end
    assert r["busy_s"] == pytest.approx(330e-9)
    assert r["op_seconds"]["%fusion.1 = f32[8] fusion()"] == \
        pytest.approx(200e-9)
    assert r["op_seconds"][events["device"][2][0]] == pytest.approx(100e-9)
    gaps = dict((round(d * 1e9), n) for n, d in r["idle_gaps"])
    # 0-100 and 300-320 in no main-thread span; 350-600 in train_run,
    # its innermost span restart covering most of it; 700-1000 mostly in
    # train_run
    assert gaps[100] == gaps[20] == "train loop (no span)"
    assert gaps[250] == "bench.restart"
    assert gaps[300] == "bench.train_run"
    assert r["device_ops"][0][0] == "fusion.1"


def test_nested_ops_count_their_own_time_once():
    events = {
        "device": [["%while.1 = () while()", 0, 100],
                   ["%fusion.1 = () fusion()", 10, 30],
                   ["%fusion.2 = () fusion()", 50, 20]],
        "host": [["bench.window", 0, 100]],
    }
    r = tracefile.reduce_events(events)
    assert r["busy_s"] == pytest.approx(100e-9)
    ops = dict(r["device_ops"])
    assert ops["while.1"] == pytest.approx(50e-9)
    assert sum(ops.values()) == pytest.approx(100e-9)


def test_module_runs_inside_the_window():
    events = {
        "device": [],
        "modules": [["jit__fused(11)", 100, 50],
                    ["jit__fused_donate(12)", 950, 100],
                    ["jit__fused_donate(12)", 1100, 10],
                    ["jit_step(3)", 200, 600]],
        "host": [["bench.window", 0, 1000]],
    }
    r = tracefile.reduce_events(events)
    # a run that straddles the window's end counts its part inside
    runs = tracefile.module_runs_matching(r, r"jit__fused(_donate)?\b")
    assert sorted(runs) == pytest.approx([50e-9, 50e-9])
    assert tracefile.module_runs_matching(r, r"jit_step\b") == \
        pytest.approx([600e-9])


def test_window_span_is_required():
    with pytest.raises(ValueError):
        tracefile.reduce_events({"device": [], "host": []})


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace(path):
    events = tracefile.read_saved(path)
    r = tracefile.reduce_events(events)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_by_host_span"].values()) == pytest.approx(idle)
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
    # the snapshot kernel reads its operand from on-chip memory (S(1)),
    # so it is not held to the HBM peak; the staging programs around it
    # read each leaf from HBM and write its packed copy back, and those
    # bytes move no faster than the HBM peak
    kernel = re.compile(r"%snapshot(\.\d+)? = .*custom-call\(u32\[[\d,]+\]"
                        r"\{[^}]*S\(1\)\}")
    assert any(kernel.match(name) for name, _, _ in events["device"])
    runs = tracefile.module_runs_matching(r, snapshot_roofline.PROGRAM)
    conf = json.loads(CONFIG.read_text())
    leaves = dense.leaf_bytes(conf["model"])
    assert runs and len(runs) % len(leaves) == 0
    saves = len(runs) // len(leaves)
    moved = saves * flops.snapshot_program_bytes(
        leaves, int(conf["craft_env"]["CRAFT_CHUNK_BYTES"]))
    assert moved / sum(runs) <= \
        1.05 * peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert "bench.update_and_write" in r["idle_by_host_span"]
