"""Measured-clock spans: the recorder's span table, the spans the live
server and its engines record, and their mirror in a profiler trace.

Contracts under test:

* **Nesting** — a span's parent is the innermost span open when it
  begins; ids, request ids and servers come back as columns.
* **One clock** — spans and marks are stamped with ``time.time_ns``.
* **Bounded** — the span table is a ring; ``spans_dropped`` counts what
  fell off, and lifecycle rows are untouched.
* **Off path** — without a recorder the engine and the server produce
  the same tokens as with one.
* **Mirror** — with ``mirror`` on, the spans appear in the profiler's
  host trace with the same names, nesting and durations.
"""
import glob
import os
import time

import jax
import numpy as np

from repro.cluster import paper_testbed
from repro.configs import get_config
from repro.core import make_policy
from repro.models import init_params
from repro.obs import TraceRecorder
from repro.obs import trace as trace_mod
from repro.serving import ServingEngine
from repro.serving.perllm_server import PerLLMServer


def _rows(sp):
    return {int(s): (str(n), int(p), int(sid), int(srv))
            for n, s, p, sid, srv in zip(sp["name"], sp["span"], sp["parent"],
                                         sp["sid"], sp["server"],
                                         strict=True)}


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_span_nesting_and_parents():
    rec = TraceRecorder()
    a = rec.begin("outer", sid=3, server=1)
    b = rec.begin("inner", 3, 1)
    rec.mark("tick", 3, 1, value=2.0)
    c = rec.then(b, "sibling")
    rec.end(c, value=5.0)
    rec.mark("top")
    rec.end(a)
    sp = rec.spans()
    rows = _rows(sp)
    ids = {name: s for s, (name, *_rest) in rows.items()}
    assert rows[ids["outer"]][1] == -1
    assert rows[ids["inner"]][1] == ids["outer"]
    assert rows[ids["tick"]][1] == ids["inner"]
    assert rows[ids["sibling"]][1] == ids["outer"]
    assert rows[ids["top"]][1] == ids["outer"]
    # `then` keeps the request and the server
    assert rows[ids["sibling"]][2:] == (3, 1)
    assert rows[ids["top"]][2:] == (-1, -1)
    # chronological, and every child inside its parent
    assert np.all(np.diff(sp["t0_ns"]) >= 0)
    by_id = {int(s): i for i, s in enumerate(sp["span"])}
    for i, p in enumerate(sp["parent"]):
        if p >= 0:
            j = by_id[int(p)]
            assert sp["t0_ns"][j] <= sp["t0_ns"][i] <= sp["t1_ns"][i] \
                <= sp["t1_ns"][j]
    marks = sp["t0_ns"] == sp["t1_ns"]
    assert set(sp["name"][marks]) >= {"tick", "top"}
    assert sp["value"][by_id[ids["sibling"]]] == 5.0
    assert sp["value"][by_id[ids["tick"]]] == 2.0
    assert rec.n_spans == 5 and rec.spans_dropped == 0


def test_unclosed_child_and_discard():
    rec = TraceRecorder()
    a = rec.begin("outer")
    rec.begin("left-open")
    rec.end(a)                 # closes the inner span too, unrecorded
    d = rec.begin("stalled")
    rec.discard(d)
    e = rec.begin("after")
    rec.end(e)
    rows = _rows(rec.spans())
    assert sorted(n for n, *_ in rows.values()) == ["after", "outer"]
    # nothing left open: the next span has no parent
    assert [p for n, p, *_ in rows.values() if n == "after"] == [-1]


def test_span_ring_is_bounded_and_lifecycle_rows_untouched():
    rec = TraceRecorder(capacity=4)
    for i in range(10):
        rec.mark("m", sid=i)
    sp = rec.spans()
    assert list(sp["sid"]) == [6, 7, 8, 9]
    assert rec.n_spans == 10 and rec.spans_dropped == 6
    assert len(rec) == 0 and rec.dropped == 0
    assert all(len(c) == 0 for c in rec.to_arrays().values())
    assert set(sp) == {"name", "span", "parent", "sid", "server", "t0_ns",
                       "t1_ns", "value"}
    empty = TraceRecorder().spans()
    assert all(len(c) == 0 for c in empty.values())


def test_spans_read_time_ns(monkeypatch):
    before = time.time_ns()
    rec = TraceRecorder()
    tok = rec.begin("real")
    rec.end(tok)
    after = time.time_ns()
    sp = rec.spans()
    assert before <= sp["t0_ns"][0] <= sp["t1_ns"][0] <= after
    ticks = iter(range(1000, 10_000, 1000))
    monkeypatch.setattr(trace_mod.time, "time_ns", lambda: next(ticks))
    rec = TraceRecorder()
    tok = rec.begin("fake")
    rec.mark("m")
    rec.end(tok)
    sp = rec.spans()
    assert list(sp["t0_ns"]) == [1000, 2000]
    assert list(sp["t1_ns"]) == [3000, 2000]


# ---------------------------------------------------------------------------
# the engine and the server
# ---------------------------------------------------------------------------

_CFG = get_config("gemma-2b").reduced(n_layers=2, d_model=64, vocab_size=256)


def _engine(**kw):
    return ServingEngine(_CFG, init_params(jax.random.key(0), _CFG),
                         max_batch=2, max_seq=64, **kw)


def _server(policy=None):
    key = jax.random.key(0)
    specs = paper_testbed(n_edge=2)[:2]
    engines = [ServingEngine(_CFG, init_params(key, _CFG), max_batch=2,
                             max_seq=64, paged=True) for _ in specs]
    return PerLLMServer(specs, engines, scheduler=make_policy(
        policy, len(specs)) if policy else None)


def _serve(srv, n=6):
    handles = [srv.submit(list(range(3, 9 + i % 4)), max_new_tokens=3,
                          deadline=4.0) for i in range(n)]
    srv.run_until_idle()
    return handles


def test_engine_spans_and_rid_fallback():
    eng = _engine(paged=True)
    rec = TraceRecorder()
    eng.attach_trace(rec, server=5)
    reqs = [eng.submit(list(range(1, 20)), max_new_tokens=4)
            for _ in range(2)]
    reqs.append(eng.submit(list(range(1, 20)), max_new_tokens=4, sid=77))
    eng.step()
    # preempt one and resume it: the page gather is its own span
    victim = eng.slot_req[eng.active_slots[0]]
    eng.evict(victim.slot)
    eng.resubmit(victim)
    eng.run_until_idle()
    sp = rec.spans()
    names = set(sp["name"])
    assert {"engine.run", "engine.admission", "engine.prefill",
            "engine.kv_store", "engine.first_token", "engine.kv_insert",
            "engine.kv_load", "engine.decode", "engine.token_wait",
            "engine.emit"} <= names
    assert set(sp["server"]) == {5}
    adm = sp["name"] == "engine.admission"
    # alone, a request carries its rid; given one, the caller's sid
    assert sorted(set(sp["sid"][adm])) == sorted({reqs[0].rid, reqs[1].rid,
                                                 77})
    assert reqs[2].sid == 77 and reqs[0].sid == reqs[0].rid
    # a fresh prompt's admission is one program: its dispatch and the wait
    # for its first token; only prefix hits (their suffix's page write and
    # slot write) and resumes (gather and slot write) dispatch KV ops
    rows = _rows(sp)
    kids = {}
    for s, (n, p, *_r) in rows.items():
        kids.setdefault(p, []).append(n)
    admissions = [kids.get(s, []) for s, (n, *_r) in rows.items()
                  if n == "engine.admission"]
    fresh = [k for k in admissions if "engine.kv_store" not in k
             and "engine.kv_load" not in k]
    assert eng.fused_admissions == len(fresh) == 1
    assert all(k == ["engine.prefill", "engine.first_token"] for k in fresh)
    names_all = list(sp["name"])
    assert names_all.count("engine.kv_store") == eng.n_prefix_hits == 2
    assert names_all.count("engine.kv_load") == 1
    assert names_all.count("engine.kv_insert") == eng.n_prefix_hits + 1
    # decode-step children hang off engine.run
    run_ids = {s for s, (n, *_r) in rows.items() if n == "engine.run"}
    for s, (n, p, *_r) in rows.items():
        if n in ("engine.decode", "engine.token_wait", "engine.emit",
                 "engine.admission"):
            assert p in run_ids, n


def test_server_sid_reaches_engine_spans():
    srv = _server()
    rec = TraceRecorder()
    srv.attach_trace(rec)
    handles = _serve(srv)
    sp = rec.spans()
    rows = list(zip(sp["name"].tolist(), sp["sid"].tolist(),
                    sp["server"].tolist(), sp["t0_ns"].tolist(),
                    sp["span"].tolist(), sp["parent"].tolist()))
    for sr in handles:
        sid = sr.service.sid
        mine = [r for r in rows if r[1] == sid]
        names = [r[0] for r in mine]
        assert names.count("server.submit") == 1
        assert names.count("server.dispatch") == 1
        assert "policy.assign" in names and "policy.feedback" in names
        disp = next(r for r in mine if r[0] == "server.dispatch")
        assert disp[2] == sr.server
        adm = [r for r in mine if r[0] == "engine.admission"]
        assert len(adm) == 1 and adm[0][2] == sr.server
        assert adm[0][3] >= disp[3]
        assert sr.engine_req.sid == sid
    # the policy's decision is timed inside the routing of its arrival
    ids = {r[4]: r for r in rows}
    for r in rows:
        if r[0] == "policy.assign":
            assert ids[r[5]][0] == "server.route"
    # lifecycle rows still land as before
    assert len(rec) > 0


def test_off_path_tokens_identical_and_nothing_recorded():
    plain, traced = _server(), _server()
    idle = TraceRecorder()
    rec = TraceRecorder()
    traced.attach_trace(rec)
    a = _serve(plain)
    b = _serve(traced)
    assert [sr.engine_req.generated for sr in a] == \
        [sr.engine_req.generated for sr in b]
    assert [sr.server for sr in a] == [sr.server for sr in b]
    assert plain.trace is None
    assert all(e.trace is None for e in plain.engines)
    assert rec.n_spans > 0 and idle.n_spans == 0
    # an engine alone, too
    e1, e2 = _engine(), _engine()
    e2.attach_trace(TraceRecorder())
    for e in (e1, e2):
        e.submit(list(range(1, 12)), max_new_tokens=5)
        e.run_until_idle()
    assert e1.completed[0].generated == e2.completed[0].generated
    # detached, the server records nothing more
    traced.attach_trace(None)
    n = rec.n_spans
    _serve(traced, n=2)
    assert rec.n_spans == n


# the perllm policy sends this burst to one edge; this baseline spreads it
# over both, so both replicas tick
SPREAD = "rewardless-guidance"


def test_fleet_engine_runs_carry_their_replica_and_change_no_token():
    plain, traced = _server(SPREAD), _server(SPREAD)
    rec = TraceRecorder()
    traced.attach_trace(rec)
    a = _serve(plain, n=10)
    b = _serve(traced, n=10)
    assert [sr.engine_req.generated for sr in a] == \
        [sr.engine_req.generated for sr in b]
    assert [sr.server for sr in a] == [sr.server for sr in b]
    rows = _rows(rec.spans())
    runs = [(p, srv) for n, p, _sid, srv in rows.values()
            if n == "engine.run"]
    # each engine tick of the event loop is one top-level engine.run,
    # labeled with the replica the loop was stepping
    assert runs and {p for p, _ in runs} == {-1}
    assert {srv for _, srv in runs} == {sr.server for sr in b} == {0, 1}


def test_engine_step_gaps_lie_between_its_runs():
    srv = _server(SPREAD)
    rec = TraceRecorder()
    srv.attach_trace(rec)
    _serve(srv, n=10)
    sp = rec.spans()
    names, servers = sp["name"], sp["server"]
    t0, t1 = sp["t0_ns"], sp["t1_ns"]
    for j, eng in enumerate(srv.engines):
        mine = (names == "engine.run") & (servers == j)
        runs = int(mine.sum())
        # a gap is counted before a step that found lanes occupied, so not
        # before the first; each lies between two of the engine's
        # engine.run spans, and is measured just outside them
        assert 1 <= eng.step_gaps <= runs - 1
        between = t0[mine][1:] - t1[mine][:-1]
        assert (between >= 0).all()
        assert 0 < eng.step_gap_ns <= between.sum()
        assert eng.metrics.get_scalar("engine.step_gaps") == eng.step_gaps


# ---------------------------------------------------------------------------
# mirrored into a profiler trace
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, "the profiler wrote no trace"
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out[ev.name] = (float(ev.start_ns),
                                    float(ev.start_ns + ev.duration_ns))
    return out


def test_mirrored_spans_in_profiler_trace(tmp_path):
    rec = TraceRecorder()
    rec.begin("engine.before")             # begun before the mirror: absent
    rec.mirror = True
    with jax.profiler.trace(str(tmp_path)):
        outer = rec.begin("engine.run")
        time.sleep(0.005)
        inner = rec.begin("engine.decode")
        time.sleep(0.005)
        rec.end(inner)
        time.sleep(0.005)
        rec.end(outer)
    rec.mirror = False
    assert not rec.mirror
    host = _host_events(str(tmp_path))
    assert set(host) == {"engine.run", "engine.decode"}
    sp = rec.spans()
    for name, t0, t1 in zip(sp["name"], sp["t0_ns"], sp["t1_ns"],
                            strict=True):
        a, b = host[str(name)]
        assert abs((b - a) - (t1 - t0)) < 1e6, name
    (ra, rb), (da, db) = host["engine.run"], host["engine.decode"]
    assert ra <= da < db <= rb
    assert (db - da) >= 4e6 and (rb - ra) >= 14e6
