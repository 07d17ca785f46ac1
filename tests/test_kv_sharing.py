"""KV page sharing & migration: ref-counted prefix index, copy-on-write
forking, and cross-server KV transfer over the link topology.

Covers the PR's invariants: any interleaving of prefix-shared allocation,
COW forking, release, export/import migration, and index reclaim conserves
the block pool — no block leaks, no block is double-freed, and every
block's refcount equals its actual holder count; nominal non-shared runs
stay bit-exact with sharing enabled; an engine prefix hit skips the shared
prefill yet generates bit-identically to a cold engine; in the simulator
shared-prefix workloads bank measurable prefill savings, a cross-server
requeue with `Decision.migrate_kv` resumes with zero re-prefill while its
transfer occupies the per-link bandwidth ledgers, and a refused migration
is counted (`n_kv_orphaned`), not silently dropped; slotted mode refuses
both knobs loudly; and the `shared-prefix` scenario shapes Zipf-reused
system-prompt pools onto the baseline workload.
"""
import copy
import dataclasses
from collections import Counter

import pytest
from hypothesis import given, settings, strategies as st

from repro.cluster import Simulator, generate_workload, paper_testbed
from repro.cluster.simulator import _EventSimRuntime
from repro.cluster.workload import classify
from repro.core import Arrival, Decision, SchedulingPolicy, make_policy
from repro.core.api import ClusterView


# ---------------------------------------------------------------------------
# PagedKVCache: sharing/COW/migration conservation (pure accounting)
# ---------------------------------------------------------------------------


_CFG = None


def _tiny_cache(n_blocks=16, block_tokens=4):
    global _CFG
    pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.serving.kvcache import PagedKVCache

    if _CFG is None:
        _CFG = get_config("gemma-2b").reduced(n_layers=2, d_model=128,
                                              vocab_size=512)
    return PagedKVCache(_CFG, n_blocks=n_blocks, block_tokens=block_tokens,
                        max_seq=32)


def _assert_conserved(cache, tables):
    """Every block's refcount equals its holder count (live tables plus
    index nodes), and unreferenced blocks are exactly the free pool."""
    held = Counter(b for t in tables for b in t.blocks)
    held += Counter(n.block for n in cache.prefix._nodes())
    for blk in range(cache.n_blocks):
        assert cache.allocator.refcount(blk) == held.get(blk, 0), blk
    assert cache.allocator.free_blocks == cache.n_blocks - len(held)


@given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 10 ** 6)),
                max_size=30))
@settings(max_examples=20, deadline=None)
def test_sharing_conservation_under_interleaving(ops):
    """Random interleavings of prefix-shared allocate / register / fork /
    release / export+import / reclaim never leak or double-free blocks."""
    cache = _tiny_cache()
    bt = cache.block_tokens
    assert cache.supports_prefix
    # three system-prompt pools of two full blocks each; suffixes vary
    pools = [list(range(64 + p * 2 * bt, 64 + (p + 1) * 2 * bt))
             for p in range(3)]
    tables = []
    for code, r in ops:
        if code == 0:                       # admit sharing a pool's prefix
            prompt = pools[r % 3] + [1 + (r // 3) % 400, 1 + (r // 7) % 400]
            t = cache.allocate(len(prompt) + 2, prompt=prompt)
            if t is not None:
                tables.append(t)
                cache.register_prefix(prompt, t)
        elif code == 1 and tables:          # copy-on-write fork
            t2 = cache.fork(tables[r % len(tables)])
            if t2 is not None:
                tables.append(t2)
        elif code == 2 and tables:          # release
            cache.free(tables.pop(r % len(tables)))
        elif code == 3 and tables:          # migrate: export, re-import, swap
            idx = r % len(tables)
            old = tables[idx]
            moved = cache.import_pages(cache.export(old), len(old.blocks))
            if moved is not None:
                tables[idx] = moved
                cache.free(old)
        else:                               # memory pressure on the index
            cache.prefix.reclaim(cache.n_blocks)
        _assert_conserved(cache, tables)
    for t in tables:
        cache.free(t)
    cache.prefix.clear()
    assert cache.allocator.free_blocks == cache.n_blocks


# ---------------------------------------------------------------------------
# Engine: prefix hits are bit-exact; non-shared runs unchanged
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_setup():
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config("gemma-2b").reduced(n_layers=2, d_model=128,
                                         vocab_size=512)
    return cfg, init_params(jax.random.key(0), cfg)


def _engine(engine_setup, **kw):
    from repro.serving import ServingEngine
    cfg, params = engine_setup
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_seq", 128)
    return ServingEngine(cfg, params, **kw)


def test_golden_disjoint_prompts_unchanged_by_sharing(engine_setup):
    """Nominal non-shared runs stay bit-exact: with no common full block
    between prompts, the sharing engine takes zero hits and generates
    exactly what a sharing-disabled engine does."""
    on = _engine(engine_setup, paged=True, kv_block_tokens=16)
    off = _engine(engine_setup, paged=True, kv_block_tokens=16,
                  prefix_sharing=False)
    prompts = [list(range(5 + i, 29 + i)) for i in range(4)]  # shifted heads
    for eng in (on, off):
        for p in prompts:
            eng.submit(list(p), max_new_tokens=6)
        eng.run_until_idle()
    assert [r.generated for r in on.completed] \
        == [r.generated for r in off.completed]
    assert on.n_prefix_hits == 0 and off.n_prefix_hits == 0
    # reclaimable-inclusive drain: the index may still hold pages, but
    # they are all surrenderable capacity
    assert on.kv.free_blocks == on.kv.n_blocks


def test_prefix_hit_skips_prefill_bit_exact(engine_setup):
    """A second request opening with a resident 2-block system prompt
    reuses those pages (skipping their prefill) and still generates
    bit-identically to a sharing-disabled engine."""
    shared = list(range(100, 132))          # 32 tokens = 2 full blocks
    p1 = shared + list(range(7, 15))
    p2 = shared + list(range(200, 208))
    cold = _engine(engine_setup, paged=True, kv_block_tokens=16,
                   prefix_sharing=False)
    warm = _engine(engine_setup, paged=True, kv_block_tokens=16)
    for eng in (cold, warm):
        eng.submit(list(p1), max_new_tokens=6)
        eng.run_until_idle()
        eng.submit(list(p2), max_new_tokens=6)
        eng.run_until_idle()
    assert [r.generated for r in warm.completed] \
        == [r.generated for r in cold.completed]
    assert warm.n_prefix_hits == 1
    assert warm.prefix_tokens_reused == 32
    assert cold.n_prefix_hits == 0
    assert warm.kv.free_blocks == warm.kv.n_blocks


# ---------------------------------------------------------------------------
# Simulator: shared-prefix ledger, migration, orphan counting
# ---------------------------------------------------------------------------


def _kv_specs(n=2, kv_blocks=64, block_tokens=64, lanes=1):
    base = paper_testbed(n_edge=max(n, 1))[:n]
    return [dataclasses.replace(s, name=f"e{i}", max_concurrency=lanes,
                                kv_blocks=kv_blocks,
                                kv_block_tokens=block_tokens)
            for i, s in enumerate(base)]


class _ScriptedPreempt(SchedulingPolicy):
    """Victim + preemptor pinned to server 0; the victim's requeue routes
    to `requeue_to`, optionally asking for a KV migration."""

    name = "scripted-preempt"

    def __init__(self, preemptor_sid, requeue_to, migrate=False):
        self.preemptor_sid = preemptor_sid
        self.requeue_to = requeue_to
        self.migrate = migrate

    def assign(self, req, view):
        if req.sid == self.preemptor_sid:
            tasks = view.running[0]
            return Decision(server=0,
                            preempt_victim=tasks[0].sid if tasks else None)
        if req.preemptions:
            return Decision(server=self.requeue_to, migrate_kv=self.migrate)
        return Decision(server=0)


class _RecordingRuntime(_EventSimRuntime):
    def __init__(self, sim, policy):
        super().__init__(sim, policy)
        self.bookings = []

    def dispatch(self, t, req, decision, **kw):
        super().dispatch(t, req, decision, **kw)
        if req.sid in self._inflight:
            self.bookings.append(self._inflight[req.sid])


def _run_migration(migrate):
    sim = Simulator(_kv_specs(), slot=None, seed=0)
    a, b = [copy.copy(s) for s in generate_workload(2, seed=0)]
    a.arrival, b.arrival = 0.0, 2.0
    a.prompt_tokens, a.output_tokens = 1024, 96
    b.prompt_tokens, b.output_tokens = 64, 8
    a.payload_bytes = b.payload_bytes = 1e6
    for r in (a, b):
        r.class_id = classify(r)
        r.preemptions = 0
        r.kv_server, r.kv_blocks = -1, 0
    rt = _RecordingRuntime(sim, _ScriptedPreempt(b.sid, requeue_to=1,
                                                 migrate=migrate))
    rt.loop.push(Arrival(0.0, requests=(a,)))
    rt.loop.push(Arrival(2.0, requests=(b,)))
    rt.drain()
    return rt, a, b


def test_migration_resumes_with_zero_reprefill_and_occupies_links():
    """Acceptance property: a cross-server requeue with `migrate_kv` ships
    the victim's pages over the topology — the continuation books a
    decode-only window (full prompt banked as savings) and the transfer
    holds every link on the union path busy for its serialization time."""
    rt, a, _ = _run_migration(migrate=True)
    assert rt.n_preempted == 1
    assert rt.n_kv_migrations == 1
    assert rt.kv_migrated_bytes > 0
    assert rt.n_kv_orphaned == 0
    assert rt.kv_prefill_tokens_saved == 1024       # zero re-prefill
    requeues = [bk for bk in rt.bookings
                if bk.request.sid == a.sid and not bk.cancelled]
    (bk,) = requeues
    assert bk.j == 1 and bk.kv_resumed
    spec = rt.specs[1]
    # decode-only: far below a full re-prefill of the 1024-token prompt
    assert bk.t_inf < spec.service_time(1024, a.output_tokens) / 0.7 \
        - spec.prefill_time(1024) / 2
    # the pages' serialization time is charged against every link on the
    # union of both servers' paths: none frees before preemption + transfer
    path = rt.topo.migration_path(0, 1)
    bw = rt.topo.migration_bandwidth(0, 1, rt._link_factors, rt.link_scale)
    dur = rt.kv_migrated_bytes * 8.0 / bw
    assert dur > 0
    assert min(rt.link_free[name] for name in path) >= 2.0 + dur * (1 - 1e-9)
    assert rt.kv_used == [0, 0]                     # ledger drains


def test_engine_migration_lands_on_destination_device():
    """Pages exported from an engine on one device and imported by an
    engine on another land on the destination's device, and the resumed
    request generates what an uninterrupted run does. Two virtual CPU
    devices, so it runs in a subprocess."""
    import os
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import jax
        from repro.configs import get_config
        from repro.models import init_params
        from repro.serving import ServingEngine
        from repro.serving.engine import Request

        cfg = get_config("gemma-2b").reduced(n_layers=2, d_model=128,
                                             vocab_size=512)
        d0, d1 = jax.devices()[:2]
        params = init_params(jax.random.key(0), cfg)

        def engine(dev):
            return ServingEngine(cfg, params, max_batch=2, max_seq=64,
                                 paged=True, kv_block_tokens=16, device=dev)

        ref, src, dst = engine(d0), engine(d0), engine(d1)
        prompt = list(range(3, 23))
        ref.submit(prompt, max_new_tokens=8)
        ref.run_until_idle()
        req = src.submit(prompt, max_new_tokens=8)
        src.step()
        src.step()
        old = src.evict(req.slot)
        table = dst.kv.import_pages(src.kv.export(old.pages),
                                    len(old.pages.blocks))
        moved = Request(rid=0, prompt=list(old.prompt), max_new_tokens=8,
                        generated=list(old.generated), pages=table,
                        kv=old.kv)
        old.kv = None
        src.release(old)
        dst.resubmit(moved)
        dst.run_until_idle()
        held = {d for leaf in jax.tree.leaves((dst.kv._pools, dst.cache))
                for d in leaf.devices()}
        assert held == {d1}, held
        assert moved.generated == ref.completed[0].generated, (
            moved.generated, ref.completed[0].generated)
        print("MIGRATED OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "MIGRATED OK" in r.stdout


def test_refused_migration_is_counted_not_silent():
    """Without `migrate_kv` the cross-server requeue abandons its pages:
    the drop is surfaced as `n_kv_orphaned` and the continuation pays the
    full re-prefill (no savings banked)."""
    rt, _, _ = _run_migration(migrate=False)
    assert rt.n_preempted == 1
    assert rt.n_kv_migrations == 0
    assert rt.n_kv_orphaned == 1
    assert rt.kv_prefill_tokens_saved == 0
    assert rt.kv_used == [0, 0]


def test_sim_shared_prefix_saves_prefill():
    """On the shared-prefix scenario the event simulator takes prefix
    hits and banks their prefill tokens; stripping the pool identities
    from the identical workload yields none."""
    specs = _kv_specs(n=2, kv_blocks=96, lanes=2)
    policy = make_policy("perllm", len(specs))
    shared = generate_workload(60, seed=3, scenario="shared-prefix")
    res = Simulator(specs, slot=None, seed=0).run(shared, policy)
    stripped = generate_workload(60, seed=3, scenario="shared-prefix")
    for r in stripped:
        r.prefix_id, r.prefix_tokens = -1, 0
    res0 = Simulator(specs, slot=None, seed=0).run(stripped, policy)
    assert res.n_prefix_hits > 0
    assert res.kv_prefill_tokens_saved > 0
    assert res0.n_prefix_hits == 0


def test_view_prefix_hit_tokens_clips_to_own_full_blocks():
    specs = _kv_specs()                     # kv_block_tokens = 64
    view = ClusterView(t=0.0, specs=specs, bw_factor=[1.0, 1.0],
                       uplink_free_at=[0.0, 0.0], lane_free=[[0.0], [0.0]],
                       running=[[], []],
                       kv_free_blocks=[64, 64], kv_total_blocks=[64, 64],
                       kv_prefix_tokens=[{7: 256}, {}])
    req = copy.copy(generate_workload(1, seed=0)[0])
    req.prompt_tokens = 280
    req.prefix_id, req.prefix_tokens = 7, 300
    # resident 256 < own full-block span min(300, 279)//64*64 = 256
    assert view.prefix_hit_tokens(req, 0) == 256
    assert view.prefix_hit_tokens(req, 1) == 0      # nothing resident
    req.prefix_tokens = 100                         # one full block only
    assert view.prefix_hit_tokens(req, 0) == 64
    req.prefix_id = -1
    assert view.prefix_hit_tokens(req, 0) == 0


# ---------------------------------------------------------------------------
# Slotted mode: loud refusal instead of silent mis-accounting
# ---------------------------------------------------------------------------


def test_slotted_construction_rejected_for_kv_workloads():
    """Slotted mode is retired; KV-sharing workloads always run on the
    event cores, so the historical slotted rejections are now a single
    construction-time error."""
    with pytest.raises(ValueError, match="slotted mode was removed"):
        Simulator(_kv_specs(), slot=0.5, seed=0)


# ---------------------------------------------------------------------------
# Scenario: Zipf-reused system-prompt pools
# ---------------------------------------------------------------------------


def test_shared_prefix_scenario_shapes_pools():
    base = generate_workload(200, seed=1)
    shaped = generate_workload(200, seed=1, scenario="shared-prefix")
    assert all(r.prefix_id >= 0 and r.prefix_tokens > 0 for r in shaped)
    # the system prompt is *prepended*: prompts grow by exactly the prefix
    by_sid = {r.sid: r for r in base}
    assert all(r.prompt_tokens
               == by_sid[r.sid].prompt_tokens + r.prefix_tokens
               for r in shaped)
    # Zipf reuse: a few pools dominate, yet more than one pool exists
    counts = Counter(r.prefix_id for r in shaped)
    assert len(counts) > 1
    assert counts.most_common(1)[0][1] > len(shaped) / len(counts)
    # arrivals stay the baseline Poisson process (request-for-request
    # comparable against the unshared workload)
    assert [r.arrival for r in shaped] == [r.arrival for r in base]
