"""Spans of the run recorder (``core/trace.py``): free when the recorder is
off, nested and parented across threads when it is on, carrying the save's
or restore's identifier, written as ``span`` lines that the replayer and
``repro.top`` read past, and timing the work inside a save and a restore."""
import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Box, Checkpoint
from repro.core import trace
from repro.core.device_snapshot import DeviceSnapshotter
from repro.core.env import CraftEnv
from repro.core.simulate import load_trace, replay


@pytest.fixture(autouse=True)
def _disarm():
    trace.uninstall()
    yield
    trace.uninstall()


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def _env(tmp_path, **extra):
    return CraftEnv.capture({
        "CRAFT_CP_PATH": str(tmp_path / "pfs"),
        "CRAFT_NODE_CP_PATH": str(tmp_path / "node"),
        **{k: str(v) for k, v in extra.items()},
    })


# ----------------------------------------------------------- recorder off
def test_null_span_is_shared_and_reads_no_clock(monkeypatch):
    calls = []
    for name in ("perf_counter_ns", "perf_counter", "monotonic", "time"):
        real = getattr(time, name)
        monkeypatch.setattr(
            time, name, lambda real=real, name=name: calls.append(name)
            or real())
    first = trace.span("cp.update", version=1)
    with first as sp:
        sp.set(rows=3)
        with trace.span("snapshot.leaf", leaf=0):
            pass
    assert trace.span("other") is first
    fn = len
    assert trace.carry(fn) is fn
    assert trace.spans() == []
    trace.flush()
    assert calls == []


def test_spans_off_still_records_events(tmp_path):
    path = tmp_path / "t.jsonl"
    trace.install(str(path), spans=False)
    assert trace.span("x") is trace._NULL_SPAN
    with trace.span("x"):
        trace.emit("step", seconds=0.5)
    trace.uninstall()
    kinds = [json.loads(x)["kind"] for x in path.read_text().splitlines()]
    assert kinds == ["step"]


# ------------------------------------------------------------ recorder on
def test_nesting_parents_and_version(tmp_path):
    trace.install(str(tmp_path / "t.jsonl"))
    with trace.span("cp.update", version=7) as outer:
        with trace.span("snapshot.leaf", leaf=3) as inner:
            inner.set(rows=2)
        with trace.span("snapshot.leaf", leaf=4, version=8):
            pass
    got = trace.spans()
    assert [s["name"] for s in got] == ["snapshot.leaf", "snapshot.leaf",
                                        "cp.update"]
    a, b, root = got
    assert root["parent"] is None and root["id"] == outer.id
    assert a["parent"] == b["parent"] == outer.id
    assert a["version"] == 7 and a["rows"] == 2 and a["leaf"] == 3
    assert b["version"] == 8               # a span's own field wins
    assert root["t0_ns"] <= a["t0_ns"] <= a["t1_ns"] <= root["t1_ns"]
    assert a["thread"] == threading.current_thread().name
    assert a["id"] != b["id"] != root["id"]


def test_carry_keeps_the_parent_on_another_thread(tmp_path):
    trace.install(str(tmp_path / "t.jsonl"))

    def work():
        with trace.span("file.write", file="a.bin"):
            pass

    with trace.span("tier.write", version=5, slot="pfs") as tier:
        worker = threading.Thread(target=trace.carry(work), name="pool-1")
        worker.start()
        worker.join()
        plain = threading.Thread(target=work, name="pool-2")
        plain.start()
        plain.join()
    carried, lost = _by_name(trace.spans(), "file.write")
    assert carried["thread"] == "pool-1" and carried["parent"] == tier.id
    assert carried["version"] == 5 and carried["slot"] == "pfs"
    # without carry the child has no parent and no identifier
    assert lost["parent"] is None and "version" not in lost


def test_flush_writes_span_lines_in_time_order(tmp_path):
    path = tmp_path / "t.jsonl"
    trace.install(str(path))
    trace.emit("step", seconds=0.1)
    with trace.span("a", version=1):
        pass
    assert len(trace.spans()) == 1
    trace.flush()
    assert trace.spans() == []            # flushed spans leave memory
    trace.emit("step", seconds=0.2)
    trace.uninstall()
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert [e["kind"] for e in lines] == ["step", "span", "step"]
    span = lines[1]
    assert span["name"] == "a" and span["version"] == 1
    assert span["t1_ns"] >= span["t0_ns"]
    assert [e["t"] for e in lines] == sorted(e["t"] for e in lines)


def test_a_full_buffer_is_written_out(tmp_path):
    path = tmp_path / "t.jsonl"
    trace.install(str(path))
    trace.TRACER.SPAN_BUFFER = 4
    for i in range(6):
        with trace.span("s", i=i):
            pass
    assert [s["i"] for s in trace.spans()] == [4, 5]
    trace.uninstall()
    spans = [json.loads(x) for x in path.read_text().splitlines()]
    assert [s["i"] for s in spans] == list(range(6))


def test_reinstall_on_the_same_path_changes_the_settings(tmp_path):
    path = str(tmp_path / "t.jsonl")
    trace.install(path, spans=False)
    tracer = trace.TRACER
    trace.install(path)
    assert trace.TRACER is tracer and tracer.spans_on
    with trace.span("a"):
        pass
    assert len(trace.spans()) == 1


def test_monitoring_listeners_are_registered_and_removed(tmp_path):
    from jax._src import monitoring

    trace.install(str(tmp_path / "t.jsonl"))
    listener = trace.TRACER._on_duration
    assert listener in monitoring._event_duration_secs_listeners
    with trace.span("train.dispatch"):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    got = trace.spans()
    outer = _by_name(got, "train.dispatch")[0]
    jit = [s for s in got if s["name"].startswith("jit.")]
    assert {"jit.trace", "jit.compile"} <= {s["name"] for s in jit}
    for s in jit:
        assert s["parent"] == outer["id"] and s["t0_ns"] <= s["t1_ns"]
    trace.install(str(tmp_path / "t.jsonl"), spans=False)
    assert listener not in monitoring._event_duration_secs_listeners
    trace.install(str(tmp_path / "t.jsonl"))
    trace.uninstall()
    assert listener not in monitoring._event_duration_secs_listeners


def test_annotate_names_each_span_in_the_profile(tmp_path, monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    trace.install(str(tmp_path / "t.jsonl"))
    with trace.span("plain"):
        pass
    trace.install(str(tmp_path / "t.jsonl"), annotate=True)
    with trace.span("cp.update"):
        with trace.span("snapshot.leaf"):
            pass
    assert seen == [("enter", "craft.cp.update"),
                    ("enter", "craft.snapshot.leaf"),
                    ("exit", "craft.snapshot.leaf"),
                    ("exit", "craft.cp.update")]


# --------------------------------------------------- where the work happens
@pytest.mark.parametrize("write_async", [0, 1])
def test_save_and_restore_spans(tmp_path, write_async):
    trace.install(str(tmp_path / "t.jsonl"))
    env = _env(tmp_path, CRAFT_WRITE_ASYNC=write_async,
               CRAFT_CHUNK_BYTES=4096, CRAFT_IO_WORKERS=3)
    box = Box({"a": jnp.arange(10000, dtype=jnp.float32),
               "b": jnp.ones((64, 64))})
    cp = Checkpoint("s", env=env)
    cp.add("state", box)
    cp.add("step", Box(1))
    cp.commit()
    assert cp.update_and_write(1)
    cp.wait()
    cp.close()
    saved = trace.spans()
    byid = {s["id"]: s for s in saved}

    def root(s):
        while s["parent"] is not None:
            s = byid[s["parent"]]
        return s["name"]

    update = _by_name(saved, "cp.update")
    assert len(update) == 1 and update[0]["version"] == 1
    # one copy at add(), one in the save
    copies = _by_name(saved, "snapshot.device_get")
    assert [s.get("version") for s in copies] == [None, 1]
    assert {s["slot"] for s in _by_name(saved, "tier.write")} == \
        {"node", "pfs"}
    for name in ("codec.encode", "file.write", "tier.publish"):
        got = _by_name(saved, name)
        assert got and all(s["version"] == 1 for s in got), name
        assert {s["slot"] for s in got} == {"node", "pfs"}, name
        assert {root(s) for s in got} == {"cp.write_version"}, name
    files = {s["file"] for s in _by_name(saved, "codec.encode")}
    assert {"leaf0-shard-0-0.bin", "leaf1-shard-0-0.bin"} <= files

    trace.flush()
    live = Box({"a": jnp.zeros(10000, jnp.float32),
                "b": jnp.zeros((64, 64))})
    cp = Checkpoint("s", env=env)
    cp.add("state", live)
    cp.add("step", Box(0))
    cp.commit()
    assert cp.restart_if_needed()
    cp.close()
    np.testing.assert_array_equal(live.value["a"], np.arange(10000))
    read = trace.spans()
    restore = _by_name(read, "cp.restore")
    assert len(restore) == 1 and restore[0]["slot"] == "node"
    for name in ("restore.materialize", "codec.read", "restore.assemble",
                 "restore.place"):
        got = _by_name(read, name)
        assert got, name
        assert all(s["version"] == 1 and s["slot"] == "node"
                   for s in got), name
    assert len(_by_name(read, "restore.place")) == 2


def test_staged_snapshot_spans(tmp_path):
    trace.install(str(tmp_path / "t.jsonl"))
    snap = DeviceSnapshotter(4096, staged=True)
    x = jnp.arange(3000, dtype=jnp.float32)
    with trace.span("cp.update", version=2):
        host, meta = snap.snapshot(0, x)
    np.testing.assert_array_equal(host, np.asarray(x))
    got = trace.spans()
    leaf = _by_name(got, "snapshot.leaf")[0]
    assert leaf["version"] == 2 and leaf["leaf"] == 0
    assert leaf["nbytes"] == 12000 and leaf["n_chunks"] == 3
    assert leaf["rows"] == 3 and leaf["donate"] is False
    for name in ("snapshot.dispatch", "snapshot.wait", "snapshot.d2h"):
        (child,) = _by_name(got, name)
        assert child["parent"] == leaf["id"] and child["version"] == 2
    assert _by_name(got, "snapshot.d2h")[0]["nbytes"] == 3 * 4096


# ------------------------------------------------------- readers of a file
def test_replay_and_top_read_a_file_with_spans(tmp_path):
    from repro import top

    tpath = tmp_path / "run.jsonl"
    env = _env(tmp_path, CRAFT_TRACE=tpath, CRAFT_TIER_EVERY="node:2,pfs:5")
    arr = np.arange(4096, dtype=np.float64)
    cp = Checkpoint("traced", env=env)
    cp.add("arr", arr)
    cp.commit()
    for it in range(12):
        arr += 1.0
        if cp.need_checkpoint(it):
            cp.update_and_write(it)
    cp.wait()
    cp.close()
    stats = dict(cp.stats)
    trace.uninstall()
    events = load_trace(tpath)
    assert any(e["kind"] == "span" for e in events)
    r = replay(events)
    assert r.decisions_match
    assert r.tier_landed["pfs"] == stats["pfs_writes"]
    m = top.model_from_trace(str(tpath))
    assert m["tiers"]["pfs"]["writes"] == stats["pfs_writes"]
    assert top.main(["--trace", str(tpath), "--once", "--no-color"]) == 0
