"""Observability: trace recorder, metrics registry, exporters.

Three contracts under test:

* **Zero-interference** — attaching a TraceRecorder changes *nothing*
  about a run: every `SimResult` field is bit-identical traced vs
  untraced, on both sim cores (golden), and the live server's outcomes
  are unchanged too.
* **Span accounting** — for every completed request the TX, QUEUE and
  INFER spans telescope exactly to its end-to-end processing time
  (conservation property; no gaps, no overlaps).
* **Export validity** — the Perfetto trace_event JSON passes the schema
  checker and the CSV round-trips the row count.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.cluster import Simulator, generate_workload, paper_testbed
from repro.core import make_policy
from repro.obs import (
    KIND_ARM, KIND_DONE, KIND_INFER, KIND_QUEUE, KIND_REJECT, KIND_TX,
    MetricsRegistry, TraceRecorder, write_csv, write_perfetto,
)
from repro.obs.export import validate_perfetto


def _run(core, trace=None, n=300, n_edge=6, rate=60.0, seed=11):
    specs = paper_testbed(n_edge=n_edge)
    sim = Simulator(specs, core=core)
    services = generate_workload(n, rate=rate, seed=seed)
    policy = make_policy("perllm", len(specs))
    return sim.run(services, policy, trace=trace)


def _fields_equal(a, b):
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray):
            assert np.array_equal(va, vb), f.name
        else:
            assert va == vb, (f.name, va, vb)


# ---------------------------------------------------------------------------
# zero-interference goldens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("core", ["array", "reference"])
def test_traced_run_bit_identical(core):
    base = _run(core)
    traced = _run(core, trace=TraceRecorder())
    _fields_equal(base, traced)


def test_cross_core_traces_identical():
    ra, rb = TraceRecorder(), TraceRecorder()
    _run("array", trace=ra)
    _run("reference", trace=rb)
    ca, cb = ra.to_arrays(), rb.to_arrays()
    assert len(ca["kind"]) == len(cb["kind"]) > 0
    for name in ca:
        assert np.array_equal(ca[name], cb[name]), name


# ---------------------------------------------------------------------------
# span accounting (conservation)
# ---------------------------------------------------------------------------

def test_span_conservation():
    rec = TraceRecorder()
    _run("array", trace=rec)
    cols = rec.to_arrays()
    kind, sid = cols["kind"], cols["sid"]
    t0, t1 = cols["t0"], cols["t1"]
    checked = 0
    for s in np.unique(sid[kind == KIND_DONE]):
        m = sid == s
        # preempted requests re-enter and own several TX windows; the
        # telescoping identity is for the single-pass lifecycle
        if np.count_nonzero(m & (kind == KIND_TX)) != 1:
            continue
        total = 0.0
        for k in (KIND_TX, KIND_QUEUE, KIND_INFER):
            i = np.flatnonzero(m & (kind == k))
            assert i.size == 1
            total += float(t1[i[0]] - t0[i[0]])
        start = float(t0[np.flatnonzero(m & (kind == KIND_TX))[0]])
        finish = float(t1[np.flatnonzero(m & (kind == KIND_DONE))[0]])
        assert total == pytest.approx(finish - start, abs=1e-9)
        checked += 1
    assert checked > 50


def test_rejects_and_arm_pulls_recorded():
    specs = paper_testbed(n_edge=2)
    sim = Simulator(specs)
    # overload a tiny testbed so admission control actually sheds
    services = generate_workload(300, rate=500.0, seed=3)
    policy = make_policy("perllm", len(specs), admission=True)
    rec = TraceRecorder()
    res = sim.run(services, policy, trace=rec)
    cols = rec.to_arrays()
    n_reject = int((cols["kind"] == KIND_REJECT).sum())
    assert n_reject == res.n_rejected > 0
    # one CSUCB arm-pull row per bandit update
    if policy.bandit is not None and rec is not None:
        assert int((cols["kind"] == KIND_ARM).sum()) == 0  # not attached
        rec2 = TraceRecorder()
        sim2 = Simulator(specs)
        pol2 = make_policy("perllm", len(specs), admission=True)
        pol2.bandit.trace = rec2
        sim2.run(generate_workload(200, rate=500.0, seed=3), pol2,
                 trace=rec2)
        assert int((rec2.to_arrays()["kind"] == KIND_ARM).sum()) > 0


# ---------------------------------------------------------------------------
# recorder unit behaviour
# ---------------------------------------------------------------------------

def test_recorder_complete_expands_to_schema_rows():
    rec = TraceRecorder()
    rec.complete(7, 1.0, 2.0, 3.5, 5.0, server=4, class_id=2, tier=1,
                 lane=3, e_tx=0.25, e_inf=1.5, tokens=64, success=True)
    cols = rec.to_arrays()
    assert len(rec) == 4 and rec.dropped == 0
    assert cols["kind"].tolist() == [KIND_TX, KIND_QUEUE, KIND_INFER,
                                     KIND_DONE]
    assert cols["t0"].tolist() == [1.0, 2.0, 3.5, 5.0]
    assert cols["t1"].tolist() == [2.0, 3.5, 5.0, 5.0]
    assert cols["sid"].tolist() == [7] * 4
    assert cols["server"].tolist() == [4] * 4
    assert cols["tier"].tolist() == [1] * 4
    assert cols["aux"].tolist() == [-1, 3, 3, -1]
    assert cols["energy"].tolist() == [0.25, 0.0, 1.5, 0.0]
    assert cols["value"].tolist() == [0.0, 0.0, 64.0, 1.0]


def test_recorder_sorts_rows_chronologically():
    rec = TraceRecorder()
    rec.append(KIND_REJECT, 9, 4.0, 4.0)
    rec.complete(1, 0.5, 1.0, 1.5, 2.0)
    rec.append(KIND_REJECT, 2, 0.25, 0.25)
    cols = rec.to_arrays()
    assert cols["t0"].tolist() == [0.25, 0.5, 1.0, 1.5, 2.0, 4.0]


def test_recorder_ring_drops_oldest():
    rec = TraceRecorder(capacity=8)
    for i in range(12):
        rec.append(KIND_REJECT, i, float(i), float(i))
    assert len(rec) == 8
    assert rec.dropped == 4
    assert rec.to_arrays()["sid"].tolist() == list(range(4, 12))
    # the completion table rings independently at capacity // 4 records
    rec = TraceRecorder(capacity=8)
    for i in range(5):
        rec.complete(i, float(i), float(i), float(i), float(i))
    assert len(rec) == 8 and rec.dropped == 12
    assert sorted(set(rec.to_arrays()["sid"].tolist())) == [3, 4]


def test_recorder_intern_and_labels():
    rec = TraceRecorder()
    a = rec.intern("0->1")
    b = rec.intern("2->1")
    assert rec.intern("0->1") == a != b
    assert rec.label(a) == "0->1" and rec.label(b) == "2->1"
    assert rec.label(99) is None
    assert rec.labels == ["0->1", "2->1"]


def test_recorder_empty_and_timeline():
    rec = TraceRecorder()
    cols = rec.to_arrays()
    assert all(len(c) == 0 for c in cols.values())
    rec.complete(5, 0.0, 1.0, 2.0, 3.0)
    rec.complete(6, 0.0, 1.0, 2.0, 3.0)
    tl = rec.timeline(5)
    assert tl["sid"].tolist() == [5] * 4


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_perfetto_export_schema(tmp_path):
    rec = TraceRecorder()
    _run("array", trace=rec, n=150)
    path = str(tmp_path / "trace.json")
    n_events = write_perfetto(rec, path)
    assert n_events > 0
    assert validate_perfetto(path) == []
    doc = json.loads(open(path).read())
    events = doc["traceEvents"]
    phases = {ev["ph"] for ev in events}
    assert "X" in phases and "M" in phases
    # every complete event carries the trace_event-required keys
    for ev in events:
        assert {"ph", "pid", "ts"} <= set(ev)
        if ev["ph"] == "X":
            assert "dur" in ev and ev["dur"] >= 0


def test_perfetto_validator_flags_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"name": "no-ph"}]}))
    assert validate_perfetto(str(bad)) != []
    assert validate_perfetto(str(tmp_path / "missing.json")) != []


def test_csv_export_row_count(tmp_path):
    rec = TraceRecorder()
    _run("array", trace=rec, n=100)
    path = str(tmp_path / "trace.csv")
    n = write_csv(rec, path)
    assert n == len(rec)
    lines = open(path).read().strip().splitlines()
    assert len(lines) == n + 1
    assert lines[0].startswith("kind,sid,t0,t1,server")


# ---------------------------------------------------------------------------
# metrics registry & canonical naming
# ---------------------------------------------------------------------------

def test_registry_counters_and_gauges():
    m = MetricsRegistry()
    m.inc("n_served")
    m.inc("n_served", 2)
    m.inc("n_served", 3, server=1)
    assert m.get_scalar("n_served") == 3
    assert m.get("n_served", server=1) == 3
    assert m.total("n_served") == 6
    m.set_gauge("kv_free_blocks", 17, server=0)
    assert m.gauge("kv_free_blocks", server=0) == 17
    assert m.gauge("kv_free_blocks", server=9, default=-1) == -1


def test_registry_histogram_observe_paths_agree():
    m = MetricsRegistry()
    m.register_histogram("lat", [0.5, 1.0, 2.0])
    vals = [0.1, 0.6, 0.6, 1.5, 9.0]
    for v in vals:
        m.observe("lat", v)
    m2 = MetricsRegistry()
    m2.register_histogram("lat", [0.5, 1.0, 2.0])
    m2.observe_many("lat", vals)
    assert m.histogram("lat") == m2.histogram("lat")
    edges, counts, total, n = m.histogram("lat")
    assert counts == [1, 2, 1, 1] and n == 5
    assert total == pytest.approx(sum(vals))
    with pytest.raises(KeyError):
        m.observe("unregistered", 1.0)


def test_registry_as_dict_snapshot():
    m = MetricsRegistry()
    m.inc("n_served", 4, server=2)
    m.set_gauge("queue_depth", 3)
    m.register_histogram("lat", [1.0])
    m.observe("lat", 0.5)
    snap = m.as_dict()
    assert snap["counters"]["n_served"]["server=2"] == 4
    assert snap["gauges"]["queue_depth"][""] == 3
    assert snap["histograms"]["lat"][""]["counts"] == [1, 0]


def test_simresult_stats_canonical_and_aliased():
    res = _run("array", n=200)
    stats = res.stats()
    assert stats["n_served"] + stats["n_rejected"] == res.n_services
    assert stats["n_served"] == sum(stats["per_server_served"])
    assert stats["avg_processing_time"] == res.avg_processing_time
    # canonical keys only: the pre-unification spellings are gone
    assert not {"served", "rejected", "mean_latency"} & set(stats)
