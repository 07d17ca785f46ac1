"""PerLLMServer: the scheduler + real-engine service loop."""
import jax
import pytest

from repro.cluster import paper_testbed
from repro.configs import get_config
from repro.models import init_params
from repro.serving import ServingEngine
from repro.serving.perllm_server import PerLLMServer


def _server(**kw):
    key = jax.random.key(0)
    edge_cfg = get_config("gemma-2b").reduced(n_layers=2, d_model=64,
                                              vocab_size=256)
    cloud_cfg = get_config("gemma3-12b").reduced(n_layers=2, d_model=128,
                                                 vocab_size=256)
    specs = paper_testbed(n_edge=2)[:2] + [paper_testbed()[-1]]
    engines = [
        ServingEngine(edge_cfg, init_params(key, edge_cfg), max_batch=2,
                      max_seq=64),
        ServingEngine(edge_cfg, init_params(key, edge_cfg), max_batch=2,
                      max_seq=64),
        ServingEngine(cloud_cfg, init_params(key, cloud_cfg), max_batch=4,
                      max_seq=64),
    ]
    return PerLLMServer(specs, engines, **kw)


def test_server_serves_all_requests():
    srv = _server()
    _reqs = [srv.submit(list(range(3, 9 + i % 4)), max_new_tokens=3,
                        deadline=4.0) for i in range(10)]
    done = srv.run_until_idle()
    assert len(done) == 10
    assert all(len(sr.engine_req.generated) == 3 for sr in done)
    stats = srv.stats
    assert stats["n_served"] == 10
    assert sum(stats["per_server_served"]) == 10
    assert 0.0 <= stats["admitted_success_rate"] <= 1.0


def test_server_learner_receives_outcomes():
    srv = _server()
    for _ in range(8):
        srv.submit([1, 2, 3, 4], max_new_tokens=2, deadline=5.0)
    srv.run_until_idle()
    # the bandit saw one update per request
    assert int(srv.scheduler.bandit.count.sum()) == 8


def test_server_trace_spans_conserve_latency():
    from repro.obs import (
        KIND_ARM, KIND_DONE, KIND_INFER, KIND_QUEUE, KIND_TX,
        TraceRecorder,
    )
    rec = TraceRecorder()
    srv = _server(trace=rec)
    for i in range(6):
        srv.submit(list(range(3, 8 + i % 3)), max_new_tokens=3,
                   deadline=4.0)
    done = srv.run_until_idle()
    assert len(done) == 6
    cols = rec.to_arrays()
    kind, sid = cols["kind"], cols["sid"]
    t0, t1 = cols["t0"], cols["t1"]
    by_sid = {sr.service.sid: sr for sr in done}
    for s, sr in by_sid.items():
        m = sid == s
        span = 0.0
        for k in (KIND_TX, KIND_QUEUE, KIND_INFER):
            i = (m & (kind == k)).nonzero()[0]
            assert i.size == 1, (s, k)
            span += float(t1[i[0]] - t0[i[0]])
        assert span == pytest.approx(sr.latency, abs=1e-9)
        d = (m & (kind == KIND_DONE)).nonzero()[0]
        assert bool(cols["value"][d[0]]) == sr.met_deadline
    # the bandit shares the recorder: one ARM row per completed request
    assert int((kind == KIND_ARM).sum()) == 6


def test_server_stats_canonical_keys_and_aliases():
    srv = _server()
    for _ in range(5):
        srv.submit([1, 2, 3, 4], max_new_tokens=2, deadline=5.0)
    srv.run_until_idle()
    stats = srv.stats
    assert stats["n_served"] == 5
    assert sum(stats["per_server_served"]) == 5
    assert stats["n_prefills"] == sum(e.n_prefills for e in srv.engines)
    # canonical keys only: the pre-unification spellings are gone
    assert not {"served", "deadline_met", "per_server"} & set(stats)
    # engine-level stats share the same canonical namespace
    est = srv.engines[0].stats()
    assert est["n_prefills"] == srv.engines[0].n_prefills
    assert "prefills" not in est
