"""The four-chip fleet cell's path on four virtual CPU devices, at test
widths: three edge replicas and a cloud replica whose model has a GQA
group of 7, one replica a device, behind the perllm policy (the layout
of `configs/yi-fleet4.json`).

One run of `run.run_cell`, in a process of its own (the device count is
fixed when JAX starts), records the harness's `Record`, the fleet and the
server's counters at the window's start. The tests read that run: it is
correct with both models compared, every per-layer reader gives a number
or None, and `replica_gap_ms` measures the gaps each engine counts in
`engine.step_gap_ns`.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
DATA = Path(__file__).parent / "data"
SEED = 2**31 + 15013
NEW = ("replica_gap_ms", "cloud_share_pct", "cloud_decode_step_ms")

SCRIPT = textwrap.dedent("""
    import dataclasses, json, math, sys, time
    sys.path.insert(0, {bench!r})
    import jax
    import run
    from chipbench import drive as D
    from chipbench import fleet as F
    from chipbench import spec as S
    from chipbench.spec import Cell

    got = {{}}
    real_build, real_record, real_open = F.build, D.Record, D.open_loop

    def counters(server):
        return {{"step_gap_ns": [e.step_gap_ns for e in server.engines],
                 "step_gaps": [e.step_gaps for e in server.engines],
                 "served": [len(e.completed) for e in server.engines]}}

    def build(*a, **kw):
        got["fleet"] = real_build(*a, **kw)
        return got["fleet"]

    def record(**kw):
        got["rec"] = real_record(**kw)
        return got["rec"]

    def open_loop(fleet, *a, **kw):
        # the last call is the window's: the fleet is idle before it
        got["before"] = counters(fleet.server)
        return real_open(fleet, *a, **kw)

    F.build, D.Record, D.open_loop = build, record, open_loop
    cfg = json.load(open({config!r}))
    mix = json.load(open({mix!r}))
    e2e = [{{"name": n, "unit": "ms"}} for n in
           ("ttft_p95_ms", "itl_p95_ms", "deadline_met_pct", "setup_s")]
    cell = Cell("fleet4-tiny", 4, "tiny-fleet4", cfg, "tiny-chat", mix,
                e2e, [])
    devs = jax.devices()
    assert len(devs) == 4, devs
    res = run.run_cell(cell, {seed}, 2.0, False, require_tpu=False,
                       devices=devs, t_start=time.time(), out=lambda s: None)
    rec, server = got["rec"], got["fleet"].server
    after = counters(server)
    names = [m["name"] for m in S.load_benchmark()["per_layer"]]
    readers = {{n: S.metric_reader(n)(rec) for n in names}}
    # the same gaps over every step the run recorded (window and drain),
    # as the program counted them from the window's start
    whole = dataclasses.replace(rec, window=(-math.inf, math.inf))
    bench_ms = S.metric_reader("replica_gap_ms")(whole)
    last, n_bench = {{}}, 0
    for s in rec.engine_steps:
        if s.engine in last and s.ctx_lens:
            n_bench += 1
        last[s.engine] = s
    print("RESULT " + json.dumps({{
        "res": res, "readers": readers, "before": got["before"],
        "after": after, "bench_gap_ms": bench_ms, "bench_gaps": n_bench,
        "devices": [str(d) for d in got["fleet"].devices]}}))
""")


@pytest.fixture(scope="module")
def fleet4():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    script = SCRIPT.format(bench=str(BENCH),
                           config=str(DATA / "tiny-fleet4.json"),
                           mix=str(DATA / "tiny-chat.json"), seed=SEED)
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    line = next(ln for ln in p.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_fleet4_correct_with_both_models(fleet4):
    res = fleet4["res"]
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert len(set(fleet4["devices"])) == 4
    checks = res["checks"]
    for name in ("tiny-edge", "tiny-cloud"):
        gap = checks[f"logit_gap.{name}"]
        assert gap["value"] <= gap["limit"]
    assert checks["routing_mismatches"]["value"] == 0
    # the window's requests went to the cloud and to more than one edge
    sent = [a - b for a, b in zip(fleet4["after"]["served"],
                                  fleet4["before"]["served"],
                                  strict=True)]
    assert sent[3] > 0 and sum(1 for n in sent[:3] if n) >= 2


def test_fleet4_new_metrics_read(fleet4):
    r = fleet4["readers"]
    for name in NEW:
        assert r[name] is not None and r[name] >= 0, (name, r[name])
    assert 0 < r["cloud_share_pct"] <= 100


def test_fleet4_every_reader_gives_a_number_or_none(fleet4):
    # an untraced run has no device trace, and a CPU has no entry in the
    # table of peaks: those readers give None
    r = fleet4["readers"]
    for name in ("decode_step_roofline", "device_idle_pct",
                 "mfu_pct.decode"):
        assert r[name] is None, (name, r[name])
    for name in ("runtime_us_per_request", "engine_wait_ms",
                 "decode_step_ms"):
        assert r[name] is not None and r[name] > 0, (name, r[name])


def test_replica_gap_agrees_with_engine_step_gap(fleet4):
    before, after = fleet4["before"], fleet4["after"]

    def delta(key):
        return sum(a - b for a, b in zip(after[key], before[key],
                                         strict=True))
    n = delta("step_gaps")
    # the same gaps: one before each step that found its lanes occupied
    assert n == fleet4["bench_gaps"] > 0
    assert delta("served") == fleet4["res"]["attempted"]
    bench_ms = fleet4["bench_gap_ms"] * n
    prog_ms = 1e-6 * delta("step_gap_ns")
    # the engine stamps just inside its `step`, the benchmark just outside
    # it, in its own wrapper: the engine's gaps are longer by the wrapper's
    # work on either side (a list of the replica's lanes, two counter
    # reads), well under half a millisecond a gap, plus clock rounding
    assert -1e-3 * n <= prog_ms - bench_ms <= 0.5 * n
