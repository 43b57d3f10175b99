"""The per-layer metrics that read the program's spans, on a hand-made run,
and the idle attribution by the program's profile events, on hand-made
events: each reader returns None where the program recorded no spans."""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import span_report, spans  # noqa: E402

NEW = ("stage_d2h_s", "stage_wait_s", "stage_host_s", "stage_launches",
       "save_idle_s", "codec_encode_s", "file_write_s", "publish_s",
       "restore_decode_s", "restore_assemble_s", "restore_place_s",
       "resume_init_s", "resume_jit_s")
S = 10 ** 9                     # ns per second; a span's clock is ns


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"t_{name}", ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(sid, name, t0, t1, parent=None, **fields):
    return {"t": 0.0, "kind": "span", "name": name, "t0_ns": int(t0 * S),
            "t1_ns": int(t1 * S), "id": sid, "parent": parent,
            "thread": "MainThread", **fields}


def make_run(tmp_path, lines, saves=(), resumes=(), window=(100.0, 200.0)):
    path = tmp_path / "craft_trace.jsonl"
    path.write_text("".join(json.dumps(x) + "\n" for x in lines))
    return SimpleNamespace(
        probes=SimpleNamespace(trace_path=path, spans=[]),
        saves=[{"version": v} for v in saves], resumes=list(resumes),
        window=SimpleNamespace(t0=window[0], t1=window[1],
                               trace_dir=tmp_path / "trace"),
        reduced={"busy_s": 1.0})


SAVE = [
    # the warm-up save (version 1) is not one of the window's saves
    span(1, "cp.update", 50.0, 51.0, version=1),
    span(2, "cp.update", 110.0, 111.0, version=2),
    span(3, "snapshot.leaf", 110.0, 110.5, 2, version=2),
    span(4, "snapshot.dispatch", 110.0, 110.1, 3, version=2),
    span(5, "snapshot.wait", 110.1, 110.15, 3, version=2),
    span(6, "snapshot.d2h", 110.15, 110.45, 3, version=2),
    span(7, "snapshot.leaf", 110.5, 110.9, 2, version=2),
    span(8, "snapshot.dispatch", 110.5, 110.6, 7, version=2),
    span(9, "snapshot.wait", 110.6, 110.62, 7, version=2),
    span(10, "snapshot.d2h", 110.62, 110.82, 7, version=2),
    span(11, "jit.compile", 110.52, 110.58, 8, version=2),
    # version 3's tier writes end after the window
    span(20, "cp.update", 150.0, 150.2, version=3),
    span(21, "codec.encode", 199.0, 199.5, version=3, slot="node"),
    span(22, "file.write", 199.5, 200.5, version=3, slot="node"),
    span(23, "tier.publish", 200.5, 200.6, version=3, slot="node"),
    span(24, "codec.encode", 120.0, 120.3, version=2, slot="pfs"),
    span(25, "file.write", 120.3, 120.9, version=2, slot="pfs"),
    span(26, "tier.publish", 120.9, 121.0, version=2, slot="pfs"),
    span(27, "codec.encode", 60.0, 61.0, version=1, slot="pfs"),
]

RESUME = [
    span(1, "train.init_state", 90.0, 91.0),          # before the window
    span(2, "train.init_state", 101.0, 102.0),
    span(3, "jit.cache_load", 101.2, 101.4, 2),
    span(4, "train.cp_setup", 102.0, 103.5),
    span(5, "cp.restore", 104.0, 110.0, version=1, slot="node"),
    span(6, "codec.read", 104.0, 105.0, 5, version=1, slot="node"),
    span(7, "restore.assemble", 105.0, 105.5, 5, version=1, slot="node"),
    span(8, "restore.place", 105.5, 105.6, 5, version=1, slot="node"),
    span(9, "train.dispatch", 111.0, 112.0),
    span(10, "jit.compile", 111.0, 111.6, 9),
    span(11, "jit.cache_load", 111.1, 111.5, 9),   # inside the compile
    span(12, "train.init_state", 121.0, 122.0),
    span(13, "codec.read", 124.0, 125.0, version=1, slot="node"),
]


def test_save_readers(tmp_path):
    run = make_run(tmp_path, SAVE, saves=(2, 3))
    got = {m: reader(m)(run) for m in
           ("stage_d2h_s", "stage_wait_s", "stage_host_s", "stage_launches",
            "codec_encode_s", "file_write_s", "publish_s")}
    assert got["stage_d2h_s"] == pytest.approx((0.3 + 0.2) / 2)
    assert got["stage_wait_s"] == pytest.approx((0.05 + 0.02) / 2)
    assert got["stage_launches"] == pytest.approx(1.0)
    # cp.update self 0.1 + 0.2; leaf selves 0.05 + 0.08; dispatch selves
    # 0.1 and 0.1 less the compile inside the second
    assert got["stage_host_s"] == pytest.approx(
        (0.1 + 0.2 + 0.05 + 0.08 + 0.1 + 0.04) / 2)
    assert got["codec_encode_s"] == pytest.approx((0.5 + 0.3) / 2)
    assert got["file_write_s"] == pytest.approx((1.0 + 0.6) / 2)
    assert got["publish_s"] == pytest.approx((0.1 + 0.1) / 2)


def test_resume_readers(tmp_path):
    run = make_run(tmp_path, RESUME, resumes=({}, {}))
    assert reader("restore_decode_s")(run) == pytest.approx(2.0 / 2)
    assert reader("restore_assemble_s")(run) == pytest.approx(0.5 / 2)
    assert reader("restore_place_s")(run) == pytest.approx(0.1 / 2)
    assert reader("resume_init_s")(run) == pytest.approx(3.5 / 2)
    # the cache load inside the compile counts once
    assert reader("resume_jit_s")(run) == pytest.approx((0.2 + 0.6) / 2)


def test_save_idle_on_the_profile_clock(tmp_path, monkeypatch):
    run = make_run(tmp_path, SAVE, saves=(2, 3))
    # the profile's clock runs 5 s ahead of the host clock
    off = 5 * S
    run.probes.spans = [{"name": "update_and_write", "t0": 109.9,
                         "t1": 111.1},
                        {"name": "update_and_write", "t0": 149.9,
                         "t1": 150.3}]
    events = {
        "host": [["bench.window", int(100 * S) + off, 100 * S],
                 ["bench.update_and_write", int(109.9 * S) + off, 1],
                 ["bench.update_and_write", int(149.9 * S) + off, 1]],
        "device": [["%op.1", int(110.2 * S) + off, int(0.3 * S)],
                   ["%op.2", int(110.4 * S) + off, int(0.2 * S)],
                   ["%op.3", int(149.0 * S) + off, int(2.0 * S)]],
    }
    monkeypatch.setattr(spans, "profile", lambda run: events)
    # cp.update v2: 1 s less 0.4 s busy; v3 is busy throughout
    assert reader("save_idle_s")(run) == pytest.approx(0.6 / 2)


@pytest.mark.parametrize("name", NEW)
def test_no_spans_reads_nothing(tmp_path, name):
    """A program that records no spans: every reader returns None."""
    events = [{"t": 0.0, "kind": "bench_clock", "host": 1.0},
              {"t": 1.0, "kind": "tier_write", "slot": "node",
               "version": 2}]
    run = make_run(tmp_path, events, saves=(2,), resumes=({},))
    assert reader(name)(run) is None


def test_idle_attribution_by_program_events():
    main, pool = "/host:CPU/main", "/host:CPU/pool"
    events = {
        "program": [["bench.window", 0, 1000, main],
                    ["bench.update_and_write", 100, 400, main],
                    ["craft.cp.update", 110, 380, main],
                    ["craft.snapshot.leaf", 120, 300, main],
                    ["craft.snapshot.d2h", 200, 200, main],
                    ["bench.train_run", 600, 300, main],
                    ["craft.train.init_state", 600, 100, main],
                    ["craft.file.write", 150, 700, pool]],
        "device": [["%a", 0, 100], ["%b", 250, 50], ["%c", 700, 300]],
    }
    att = span_report.attribute(events)
    assert att["window_s"] == pytest.approx(1000e-9)
    assert att["idle_s"] == pytest.approx(550e-9)
    idle = {k: {n: round(v * 1e9) for n, v in d.items()}
            for k, d in att["idle_by_span"].items()}
    none = span_report.NONE
    # gaps 100-250 and 300-700; the second runs out of update_and_write
    # at 500 and into train_run at 600
    assert idle["bench.update_and_write"] == {
        none: 20, "craft.cp.update": 80, "craft.snapshot.leaf": 100,
        "craft.snapshot.d2h": 150}
    assert idle["bench.train_run"] == {"craft.train.init_state": 100}
    assert idle[none] == {none: 100}
    inside = att["idle_inside"]
    assert sum(inside["craft.cp.update"].values()) == pytest.approx(330e-9)
    assert inside["bench.train_run"] == pytest.approx(
        {"craft.train.init_state": 100e-9})
    longest = att["longest_gaps"][0]
    assert longest["seconds"] == pytest.approx(400e-9)
    assert longest["other_threads"][0][:2] == ["craft.file.write", pool]
    share = span_report.named_share(inside["bench.update_and_write"],
                                    ("craft.cp.update",))
    assert share == pytest.approx(250 / 350)
    cov = span_report.coverage(events)
    assert cov["cp_update_s"] == pytest.approx(380e-9)
    assert cov["update_and_write_s"] == pytest.approx(400e-9)
    assert cov["leaves_cover_of_cp_update"] == pytest.approx(300 / 380)
