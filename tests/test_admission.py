"""Admission control, preemption, and the per-link bandwidth topology.

Covers the PR's invariants: rejected requests consume no server energy and
surface as SLO misses; preemption never oversubscribes a lane (the victim's
lane is free before the preemptor's InferStart) and requeues the victim's
remaining decode tokens; a link's fluctuation trace is invariant to cluster
size (`LinkTopology` substreams — the `BandwidthModel` RNG-coupling fix);
and with everything disabled the degenerate topology reproduces the legacy
runtime bit-for-bit.
"""
import copy
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.cluster import (
    BandwidthModel, Link, LinkTopology, Simulator, generate_workload,
    make_topology, paper_testbed,
)
from repro.cluster.simulator import _EventSimRuntime
from repro.cluster.workload import classify
from repro.core import (
    Arrival, Decision, SchedulingPolicy, make_policy, make_scenario,
)


# ---------------------------------------------------------------------------
# LinkTopology: structure + the per-link RNG substream fix
# ---------------------------------------------------------------------------


def _one_lane_spec(name="edge0", bandwidth=100e6):
    base = paper_testbed(n_edge=1)[0]
    return dataclasses.replace(base, name=name, bandwidth=bandwidth,
                               max_concurrency=1)


def test_link_trace_invariant_to_cluster_size():
    """The legacy model's shared RNG couples a link's noise to how many
    links exist; LinkTopology substreams do not."""
    def topo(n_links):
        links = [Link(f"l{i}", 1e8, fluctuating=True) for i in range(n_links)]
        return LinkTopology(links, [[lk.name] for lk in links], seed=7)

    small, big = topo(2), topo(6)
    trace_small = [small.factor("l1", k) for k in range(50)]
    trace_big = [big.factor("l1", k) for k in range(50)]
    assert trace_small == trace_big
    # sampling other links first must not perturb the trace either
    mixed = []
    for k in range(50):
        big.factor("l3", k)
        big.factor("l5", k)
        mixed.append(big.factor("l1", k))
    assert mixed == trace_big
    # the legacy model is order-coupled (documented defect, kept for the
    # golden shim): the same draw differs once another draw precedes it
    m1 = BandwidthModel(fluctuating=True, seed=7)
    m2 = BandwidthModel(fluctuating=True, seed=7)
    a = m1.factor(0, 1)
    m2.factor(0, 0)
    b = m2.factor(0, 1)
    assert a != b


def test_degenerate_topology_is_bit_exact_with_default():
    """Passing the explicit degenerate topology == passing none, in both
    event cores (the golden guarantee the rewrite rides on)."""
    specs = paper_testbed("llama2-7b")
    wl = generate_workload(300, seed=0)
    for core in ("array", "reference"):
        results = []
        for explicit in (False, True):
            bw = BandwidthModel(fluctuating=True, seed=1)
            sim = Simulator(
                specs, bw, seed=42, core=core,
                topology=LinkTopology.degenerate(specs, bw)
                if explicit else None)
            results.append(sim.run([copy.copy(s) for s in wl],
                                   make_policy("perllm", len(specs))))
        assert results[0] == results[1]


def test_shared_backhaul_serializes_cloud_transfers():
    """In the edge-cloud topology, cloud-bound transfers traverse
    user-cloud + the shared edge-cloud backhaul; scaling the backhaul to
    near-zero throttles the cloud even though its access link is healthy."""
    specs = paper_testbed()
    cloud = len(specs) - 1

    class PinCloud(SchedulingPolicy):
        name = "pin-cloud"

        def assign(self, req, view):
            return Decision(server=cloud)

    topo = LinkTopology.edge_cloud(specs)
    assert topo.paths[cloud] == ["user-cloud", "edge-cloud"]
    sc = make_scenario("cloud-outage", scale=0.02, start_frac=0.0,
                       stop_frac=1.0)
    for core in ("array", "reference"):
        wl = generate_workload(80, seed=4)
        base = Simulator(specs, seed=3, core=core,
                         topology=LinkTopology.edge_cloud(specs)).run(
            [copy.copy(s) for s in wl], PinCloud())
        degraded = Simulator(specs, seed=3, core=core,
                             topology=LinkTopology.edge_cloud(specs)).run(
            [copy.copy(s) for s in wl], PinCloud(), scenario=sc)
        assert degraded.avg_processing_time > 2 * base.avg_processing_time
    with pytest.raises(KeyError, match="unknown topology"):
        make_topology("mesh", specs)


def test_view_exposes_link_state():
    specs = paper_testbed()
    sim = Simulator(specs, slot=None, seed=0,
                    topology=LinkTopology.edge_cloud(specs))
    seen = {}

    class Peek(SchedulingPolicy):
        name = "peek"

        def assign(self, req, view):
            seen.update(bw=view.link_bw, q=view.link_queue,
                        paths=view.paths, running=view.running)
            return Decision(server=0)

    sim.run([copy.copy(s) for s in generate_workload(5, seed=0)], Peek())
    assert set(seen["bw"]) == {"user-edge0", "user-edge1", "user-edge2",
                               "user-edge3", "user-edge4", "user-cloud",
                               "edge-cloud"}
    assert all(v >= 0 for v in seen["q"].values())
    assert seen["paths"][-1] == ["user-cloud", "edge-cloud"]
    assert isinstance(seen["running"], list)


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


class RejectAll(SchedulingPolicy):
    name = "reject-all"

    def __init__(self):
        self.feedback_log = []

    def assign(self, req, view):
        return Decision(server=0, admit=False)

    def feedback(self, req, out):
        self.feedback_log.append(out)


def test_rejected_requests_consume_no_server_energy():
    """A shed request never touches a server: zero tx/infer energy, no
    served count, success False, and the rejected Outcome still reaches
    the policy's feedback with the SLO-violation cost."""
    specs = paper_testbed()
    for core in ("array", "reference"):
        policy = RejectAll()
        wl = [copy.copy(s) for s in generate_workload(40, seed=2)]
        res = Simulator(specs, seed=0, core=core).run(wl, policy)
        assert res.n_rejected == 40
        assert res.success_rate == 0.0
        assert res.e_tx == 0.0 and res.e_infer == 0.0
        assert res.per_server_served == [0] * len(specs)
        assert len(policy.feedback_log) == 40
        for req, out in zip(sorted(wl, key=lambda r: r.arrival),
                            policy.feedback_log, strict=True):
            assert out.rejected and not out.success
            assert out.energy == 0.0
            assert out.processing_time == pytest.approx(2.0 * req.deadline)


def test_admission_improves_admitted_slo_under_overload():
    """The acceptance bar: under sustained overload, PerLLM+admission has
    strictly higher admitted-request SLO satisfaction than always-admit
    PerLLM (which degrades everyone uniformly)."""
    specs = paper_testbed("llama2-7b")
    wl = generate_workload(1200, rate=10.0, seed=0, scenario="overload")
    runs = {}
    for admission in (False, True):
        sim = Simulator(specs, BandwidthModel(seed=1), seed=42)
        runs[admission] = sim.run(
            [copy.copy(s) for s in wl],
            make_policy("perllm", len(specs), admission=admission))
    always = runs[False]
    gated = runs[True]
    assert always.n_rejected == 0
    assert gated.n_rejected > 0
    # admitted-SLO strictly better, and better than always-admit's overall
    assert gated.admitted_success_rate > always.admitted_success_rate
    assert gated.success_rate > always.success_rate


def test_rejection_does_not_poison_perllm_estimators():
    policy = make_policy("perllm", 2, admission=True)
    ratio_before = policy.infer_ratio.copy()
    req = copy.copy(generate_workload(1, seed=0)[0])
    req.class_id = classify(req)
    from repro.cluster.simulator import Outcome
    out = Outcome(server=1, tx_time=0.0, queue_time=0.0, infer_time=0.0,
                  finish=0.0, processing_time=2 * req.deadline,
                  success=False, energy=0.0, rejected=True)
    policy.feedback(req, out)
    assert np.array_equal(policy.infer_ratio, ratio_before)
    assert policy.ratio_count.sum() == 0


# ---------------------------------------------------------------------------
# Preemption
# ---------------------------------------------------------------------------


class PreemptFor(SchedulingPolicy):
    """Pins everything to server 0; the request with sid == `preemptor`
    preempts whatever is running there (the runtime decides legality)."""

    name = "preempt-for"

    def __init__(self, preemptor_sid):
        self.preemptor_sid = preemptor_sid

    def assign(self, req, view):
        victim = None
        if req.sid == self.preemptor_sid and view.running:
            tasks = view.running[0]
            if tasks:
                victim = tasks[0].sid
        return Decision(server=0, preempt_victim=victim)


class _RecordingRuntime(_EventSimRuntime):
    """Captures every booking and preemption for invariant checks."""

    def __init__(self, sim, policy):
        super().__init__(sim, policy)
        self.bookings = []
        self.preempts = []        # (time, victim booking)

    def dispatch(self, t, req, decision):
        super().dispatch(t, req, decision)
        self.bookings.append(self._inflight[req.sid])

    def on_preempt(self, ev):
        victim = self._inflight.get(ev.victim)
        super().on_preempt(ev)
        if victim is not None and victim.cancelled:
            self.preempts.append((ev.time, victim))


def _run_preemption(t_victim, t_preemptor):
    """One-lane server; a long-decode victim and a later preemptor."""
    spec = _one_lane_spec()
    sim = Simulator([spec], slot=None, seed=0)
    a, b = [copy.copy(s) for s in generate_workload(2, seed=0)]
    a.arrival, b.arrival = float(t_victim), float(t_victim + t_preemptor)
    a.prompt_tokens, a.output_tokens = 1024, 96     # long-running victim
    b.prompt_tokens, b.output_tokens = 64, 8
    a.payload_bytes = b.payload_bytes = 1e6
    for r in (a, b):
        r.class_id = classify(r)
        r.preemptions = 0
    rt = _RecordingRuntime(sim, PreemptFor(b.sid))
    rt.loop.push(Arrival(a.arrival, requests=(a,)))
    rt.loop.push(Arrival(b.arrival, requests=(b,)))
    rt.drain()
    return rt, a, b


@given(st.floats(0.0, 2.0), st.floats(0.05, 10.0))
@settings(max_examples=25, deadline=None)
def test_preempted_lane_free_before_preemptors_infer_start(t_victim,
                                                           t_preemptor):
    """Lanes are never oversubscribed under preemption: on a one-lane
    server, the effective busy intervals of all bookings are disjoint, and
    the victim's lane is returned no later than the preemptor's
    InferStart."""
    rt, a, b = _run_preemption(t_victim, t_preemptor)
    assert rt.n_preempted == len(rt.preempts)
    # every booking's effective interval: truncated at preemption time
    intervals = []
    preempt_at = {id(v): t for t, v in rt.preempts}
    for bk in rt.bookings:
        end = preempt_at.get(id(bk), bk.finish) if bk.cancelled else bk.finish
        start = bk.begin
        if end > start:
            intervals.append((start, end))
    intervals.sort()
    for (_s1, e1), (s2, _e2) in zip(intervals, intervals[1:], strict=False):
        assert e1 <= s2 + 1e-9, f"lane oversubscribed: {intervals}"
    # the preemptor's own booking starts at/after the preemption instant
    for t, _victim in rt.preempts:
        preemptor_bookings = [bk for bk in rt.bookings
                              if bk.request.sid == b.sid]
        assert preemptor_bookings
        assert all(bk.begin >= t - 1e-9 for bk in preemptor_bookings)
    # both requests eventually complete exactly once each
    assert len(rt.outcomes) == 2
    assert {o.server for o in rt.outcomes} == {0}


def test_preemption_requeues_remaining_tokens():
    rt, a, b = _run_preemption(0.0, 1.0)
    assert rt.n_preempted == 1
    assert a.preemptions == 1
    assert 0 < a.output_tokens <= 96      # remaining decode tokens only
    assert a.finish > 0 and b.finish > 0
    # the victim's final outcome spans its whole life (SLO unchanged)
    victim_out = [o for o in rt.outcomes if o.finish == a.finish][0]
    assert victim_out.processing_time == pytest.approx(a.finish - a.arrival)


def test_slotted_construction_rejected():
    """Slotted mode is retired: a numeric `slot=` fails at construction
    with a migration-pointing error, so a policy that relies on event
    semantics (e.g. preemption) can never land in a quantized runtime."""
    spec = _one_lane_spec()
    with pytest.raises(ValueError, match="slotted mode was removed"):
        Simulator([spec], slot=0.5, seed=0)


def test_live_server_preempts_engine_slot():
    """PerLLMServer preemption: the victim is evicted from its engine slot
    (ServingEngine.evict) and requeued with its remaining tokens; both
    requests still complete."""
    pytest.importorskip("jax")
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import ServingEngine
    from repro.serving.perllm_server import PerLLMServer

    class PreemptLatest(SchedulingPolicy):
        name = "preempt-latest"

        def __init__(self):
            self.armed = False

        def assign(self, req, view):
            victim = None
            if self.armed and view.running and view.running[0]:
                victim = view.running[0][0].sid
            return Decision(server=0, preempt_victim=victim)

    cfg = get_config("gemma-2b").reduced(n_layers=1, d_model=32,
                                         vocab_size=128)
    specs = [_one_lane_spec()]
    engines = [ServingEngine(cfg, init_params(jax.random.key(0), cfg),
                             max_batch=1, max_seq=64)]
    policy = PreemptLatest()
    srv = PerLLMServer(specs, engines, scheduler=policy)
    first = srv.submit([1, 2, 3], max_new_tokens=12, payload_bytes=1e4)
    for _ in range(60):
        if srv.engines[0].active_slots:
            break
        srv.step()
    assert srv.engines[0].active_slots
    policy.armed = True
    second = srv.submit([4, 5], max_new_tokens=2, payload_bytes=1e4)
    done = srv.run_until_idle()
    assert srv.n_preempted == 1
    assert first.service.preemptions == 1
    assert first.service.output_tokens < 12        # only the remainder
    assert {sr.service.sid for sr in done} \
        == {first.service.sid, second.service.sid}
    assert not srv.rejected


def test_live_server_rejects_cleanly():
    pytest.importorskip("jax")
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import ServingEngine
    from repro.serving.perllm_server import PerLLMServer

    cfg = get_config("gemma-2b").reduced(n_layers=1, d_model=32,
                                         vocab_size=128)
    specs = [_one_lane_spec()]
    engines = [ServingEngine(cfg, init_params(jax.random.key(0), cfg),
                             max_batch=1, max_seq=64)]
    policy = RejectAll()
    srv = PerLLMServer(specs, engines, scheduler=policy)
    srv.submit([1, 2, 3], max_new_tokens=4)
    done = srv.run_until_idle()
    assert done == []
    assert len(srv.rejected) == 1
    assert srv.stats["n_rejected"] == 1
    (out,) = policy.feedback_log
    assert out.rejected and out.energy == 0.0


def test_perllm_preempt_only_targets_doomed_tasks():
    """PerLLM's victim search only fires when the candidate is already
    missing its own deadline; a healthy cluster never preempts."""
    specs = paper_testbed("llama2-7b")
    wl = generate_workload(400, rate=8.0, seed=0)
    sim = Simulator(specs, slot=None, seed=42)
    res = sim.run([copy.copy(s) for s in wl],
                  make_policy("perllm", len(specs), admission=True,
                              preempt=True))
    assert res.n_preempted == 0      # nothing doomed at this load
    assert res.success_rate > 0.9
