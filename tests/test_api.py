"""Unified SchedulingPolicy API: Decision/Allocation semantics, registry,
runtime-applied commits, and the retirement of the legacy batch protocol.

The pre-PR-1 `SchedulerBase`/`as_policy` deprecation shims are gone
(nothing in-tree subclassed them since PR 1); the golden coverage that the
Decision path reproduces the seed protocol lives on in
`tests/test_runtime.py` (frozen PR-1 slot loop) and
`tests/test_allocation.py` (nominal-tier bit-exactness).
"""
import copy
import math

import numpy as np
import pytest

from repro.cluster import (
    BandwidthModel, ClusterView, Simulator, generate_workload, paper_testbed,
)
from repro.core import (
    CSUCB, CSUCBParams, Decision, SchedulingPolicy, available_policies,
    drive_slot, ensure_policy, make_policy,
)


# ---------------------------------------------------------------------------
# Decision semantics
# ---------------------------------------------------------------------------


def test_policies_do_not_mutate_requests():
    """Deferral is Decision data — FineInfer never stamps `req.defer_until`
    onto requests."""
    specs = paper_testbed()
    services = [copy.copy(s) for s in generate_workload(150, seed=1)]
    sim = Simulator(specs, BandwidthModel(), seed=1)
    sim.run(services, make_policy("fineinfer", len(specs)))
    assert not any(hasattr(r, "defer_until") for r in services)


def test_fineinfer_defer_applied_by_runtime():
    """Deferred batching still delays dispatch (tx starts at the window)."""
    specs = paper_testbed()
    services = [copy.copy(s) for s in generate_workload(80, seed=1)]
    sim = Simulator(specs, BandwidthModel(), seed=1)
    sim.run(services, make_policy("fineinfer", len(specs),
                                  batch_window=1.0))
    # every request finishes after its batching-window boundary
    for r in sorted(services, key=lambda r: r.sid):
        assert r.finish >= math.ceil(r.arrival / 1.0) * 1.0


def test_drive_slot_commits_residuals():
    """The runtime, not the policy, consumes capacity per Decision."""
    specs = paper_testbed()
    view = ClusterView(t=0.0, specs=specs, bw_factor=[1.0] * len(specs),
                       uplink_free_at=[0.0] * len(specs),
                       lane_free=[[0.0] * s.max_concurrency for s in specs])

    class Fixed(SchedulingPolicy):
        def assign(self, req, v):
            return Decision(server=0)

    services = generate_workload(5, seed=0)
    from repro.cluster.workload import classify
    for s in services:
        s.class_id = classify(s)
    before = view.uplink_free_at[0]
    decisions = drive_slot(Fixed(), services, view)
    assert [d.server for d in decisions] == [0] * 5
    assert view.uplink_free_at[0] > before
    assert sorted(view.lane_free[0]) != [0.0] * specs[0].max_concurrency


def test_decision_defaults_are_nominal_allocation():
    """A bare Decision carries the nominal Allocation: nominal tier, full
    lane and uplink shares — the placement-only contract."""
    d = Decision(server=2)
    assert d.alloc.freq_tier == -1
    assert d.alloc.lane_share == 1.0
    assert d.alloc.bw_share == 1.0


# ---------------------------------------------------------------------------
# Legacy protocol retirement
# ---------------------------------------------------------------------------


def test_legacy_scheduler_base_protocol_removed():
    """The batch `SchedulerBase` shims are gone from both packages, and a
    batch-protocol object is rejected with a migration pointer rather
    than silently wrapped."""
    import repro.cluster as cluster
    import repro.core as core
    for name in ("SchedulerBase", "as_policy", "LegacyPolicyAdapter",
                 "SlotView"):
        assert not hasattr(core, name), name
        assert not hasattr(cluster, name), name

    class OldStyle:
        def schedule(self, arrivals, view, t_slot):
            return [0] * len(arrivals)

    with pytest.raises(TypeError, match="SchedulerBase batch protocol"):
        ensure_policy(OldStyle())
    with pytest.raises(TypeError, match="SchedulingPolicy"):
        Simulator(paper_testbed(), seed=0).run(
            [copy.copy(s) for s in generate_workload(3, seed=0)],
            OldStyle())


def test_scheduling_policy_has_no_batch_shim_methods():
    p = make_policy("perllm", 6)
    assert not hasattr(p, "observe")
    assert not callable(getattr(p, "schedule", None))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_round_trip():
    for name in ("perllm", "PerLLM", "FineInfer", "agod",
                 "rewardless-guidance", "RewardlessGuidance"):
        p = make_policy(name, 6)
        assert isinstance(p, SchedulingPolicy)
    assert {"agod", "fineinfer", "perllm", "rewardless-guidance"} \
        <= set(available_policies())


def test_registry_kwargs_forwarded():
    p = make_policy("fineinfer", 6, batch_window=2.5)
    assert p.batch_window == 2.5
    p = make_policy("perllm", 4, params=CSUCBParams(delta=0.123))
    assert p.bandit.p.delta == 0.123
    assert p.n_servers == 4


def test_registry_unknown_name_raises():
    with pytest.raises(KeyError, match="unknown scheduling policy"):
        make_policy("nope-not-a-policy", 6)


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------


def test_csucb_ucb_is_side_effect_free():
    bandit = CSUCB(1, 3)
    mask = np.ones(3, bool)
    t0 = bandit.t
    s1 = bandit.ucb(0, mask)
    s2 = bandit.ucb(0, mask)
    assert bandit.t == t0            # scoring does not advance bandit time
    assert np.array_equal(s1, s2)    # double scoring is idempotent
    bandit.select(0, mask)
    assert bandit.t == t0
    bandit.update(0, 0, 0.5, 0.0)
    assert bandit.t == t0 + 1        # time advances only on feedback


def test_csucb_regret_bound_tracks_arm_space():
    """Satellite bugfix: Eq. 7's arm count comes from the live arm-space
    shape, so a (class, server, tier) bandit reports a wider bound than
    its placement-only projection at the same pull counts."""
    flat = CSUCB(2, 3)
    tiered = CSUCB(2, 3, n_tiers=4)
    for b in (flat, tiered):
        b.update(0, 1, 0.1, 0.0)
        b.update(0, 1, 0.1, 0.0)
        b.update(0, 1, 0.1, 0.0)
    assert flat.regret_bound() == pytest.approx(
        math.sqrt(2.0 * 2 * 3 * math.log(3)))
    assert tiered.regret_bound() == pytest.approx(
        math.sqrt(2.0 * 2 * 3 * 4 * math.log(3)))
    assert tiered.regret_bound() > flat.regret_bound()


def test_simulator_empty_services():
    specs = paper_testbed()
    res = Simulator(specs, seed=0).run([], make_policy("perllm", len(specs)))
    assert res.n_services == 0
    assert res.success_rate == 0.0
    assert res.total_energy == 0.0
    assert res.makespan == 0.0
    assert res.per_server_served == [0] * len(specs)


def test_perllm_server_view_not_degenerate():
    """The live server observes real bandwidth factors and persistent
    uplink state (previously hardcoded to 1.0 / clock)."""
    pytest.importorskip("jax")
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import ServingEngine
    from repro.serving.perllm_server import PerLLMServer

    cfg = get_config("gemma-2b").reduced(n_layers=1, d_model=32,
                                         vocab_size=128)
    key = jax.random.key(0)
    specs = paper_testbed(n_edge=1)[:1] + [paper_testbed()[-1]]
    engines = [ServingEngine(cfg, init_params(key, cfg), max_batch=2,
                             max_seq=32) for _ in range(2)]
    srv = PerLLMServer(specs, engines,
                       bandwidth=BandwidthModel(fluctuating=True, seed=3))
    for _ in range(4):
        srv.submit([1, 2, 3], max_new_tokens=2, payload_bytes=4e6)
    srv.step()
    # routing committed real uplink occupancy that persists across steps
    assert max(srv.uplink_free_at) > 0.0
    view = srv._view()
    assert list(view.uplink_free_at) == list(srv.uplink_free_at)
    assert any(f != 1.0 for f in view.bw_factor)
    srv.run_until_idle()
    assert srv.stats["n_served"] == 4


def test_perllm_server_applies_defer_until():
    """The live runtime honors Decision.defer_until: deferred-batching
    requests are held out of the engines until their window boundary."""
    pytest.importorskip("jax")
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import ServingEngine
    from repro.serving.perllm_server import PerLLMServer

    cfg = get_config("gemma-2b").reduced(n_layers=1, d_model=32,
                                         vocab_size=128)
    key = jax.random.key(0)
    specs = paper_testbed(n_edge=1)[:1] + [paper_testbed()[-1]]
    engines = [ServingEngine(cfg, init_params(key, cfg), max_batch=2,
                             max_seq=32) for _ in range(2)]
    srv = PerLLMServer(specs, engines,
                       scheduler=make_policy("fineinfer", 2,
                                             batch_window=1.0))
    srv.step()                       # advance the clock off zero
    assert 0.0 < srv.clock < 1.0
    sr = srv.submit([1, 2, 3], max_new_tokens=2)
    srv.step()                       # routed: window boundary is at t=1.0
    assert sr.decision.defer_until == 1.0
    assert sr.engine_req is None     # held — not yet in any engine
    assert sr in srv._deferred
    done = srv.run_until_idle()
    assert sr in done and sr.done
    assert sr.done_clock >= 1.0      # dispatched only after the window