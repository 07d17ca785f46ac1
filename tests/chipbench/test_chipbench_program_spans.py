"""The program's own spans, as the benchmark reads them: the trace
reduction with program spans nested in the benchmark's, the readers of
`chipbench.programspans` on hand-built span tables, and the readers over
a tiny fleet driven on the CPU with a recorder attached."""
import json
import math
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import drive as D
from chipbench import programspans as PS
from chipbench import trace as T

DATA = Path(__file__).parent / "data"
SEED = 2**31 + 8191
MS = 1_000_000            # ns


# ---------------------------------------------------------------------------
# the trace reduction
# ---------------------------------------------------------------------------

def _bench_trace(extra_host=()):
    host = [["bench.step", 0, 100], ["engine.step#0", 10, 90],
            ["bench.wait", 100, 150],
            ["bench.step", 150, 260], ["engine.step#1", 160, 250],
            *extra_host]
    dev = [[20, 40, "fusion.1"], [50, 60, "fusion.2"],
           [170, 230, "fusion.3"], [232, 240, "argmax"]]
    mods = [[20, 60, "jit__prefill_cache"], [170, 230, "jit_decode_step"],
            [232, 240, "jit_argmax"]]
    return {"host": host, "devices": {"/device:TPU:0": dev},
            "modules": {"/device:TPU:0": mods}}


def test_reduce_unchanged_by_nested_program_spans():
    kinds = {0: "engine.admit", 1: "engine.decode"}
    plain = T.reduce(_bench_trace(), kinds)
    program = [["engine.run", 11, 89], ["engine.admission", 12, 70],
               ["engine.prefill", 15, 47], ["engine.kv_insert", 60, 69],
               ["engine.run", 161, 249], ["engine.decode", 162, 169],
               ["engine.token_wait", 169, 240], ["engine.emit", 241, 248]]
    nested = T.reduce(_bench_trace(program), kinds)
    for key in ("window_ns", "busy_ns", "steps", "device_ops"):
        assert nested[key] == plain[key], key
    gaps = [d for _, d in plain["idle_gaps"]]
    assert [d for _, d in nested["idle_gaps"]] == gaps
    # a gap inside an engine step is named by the program's innermost
    # span open there, not by the step's kind
    assert {n for n, _ in plain["idle_gaps"]} == {
        "bench.wait", "engine.admit", "engine.decode", "bench.route"}
    assert {n for n, _ in nested["idle_gaps"]} == {
        "bench.wait", "engine.prefill", "engine.token_wait", "bench.route"}
    assert nested["idle_gaps"][0][0] == "bench.wait"


# ---------------------------------------------------------------------------
# the readers on a hand-built table
# ---------------------------------------------------------------------------

def _table(rows):
    """rows: (name, span, parent, sid, server, t0_ms, t1_ms)."""
    cols = list(zip(*rows))
    return {"name": np.array(cols[0], dtype=str),
            "span": np.array(cols[1], dtype=np.int64),
            "parent": np.array(cols[2], dtype=np.int64),
            "sid": np.array(cols[3], dtype=np.int64),
            "server": np.array(cols[4], dtype=np.int32),
            "t0_ns": np.array([round(t * MS) for t in cols[5]], np.int64),
            "t1_ns": np.array([round(t * MS) for t in cols[6]], np.int64),
            "value": np.zeros(len(rows))}


def _record():
    rows = [
        # two requests routed, dispatched to engine 0 and admitted there
        ("server.submit", 0, -1, 0, -1, 10, 10),
        ("server.route", 1, -1, 0, -1, 10, 11),
        ("policy.assign", 2, 1, 0, -1, 10.2, 10.5),
        ("server.submit", 3, -1, 1, -1, 12, 12),
        ("server.route", 4, -1, 1, -1, 12, 13),
        ("policy.assign", 5, 4, 1, -1, 12.1, 12.3),
        ("server.dispatch", 6, -1, 0, 0, 15, 15),
        ("server.dispatch", 7, -1, 1, 0, 16, 16),
        # engine 0: a step admitting both, then three decode-only steps
        ("engine.run", 10, -1, -1, 0, 18, 40),
        ("engine.admission", 11, 10, 0, 0, 20, 30),
        ("engine.first_token", 12, 11, 0, 0, 26, 28),
        ("engine.admission", 13, 10, 1, 0, 30, 36),
        ("engine.first_token", 14, 13, 1, 0, 34, 35),
        ("engine.decode", 15, 10, -1, 0, 36, 37),
        ("engine.token_wait", 16, 10, -1, 0, 37, 39),
        ("engine.emit", 17, 10, -1, 0, 39, 40),
        ("engine.run", 20, -1, -1, 0, 42, 50),
        ("engine.decode", 21, 20, -1, 0, 42, 43),
        ("engine.token_wait", 22, 20, -1, 0, 43, 48),
        ("engine.emit", 23, 20, -1, 0, 48, 50),
        ("engine.run", 30, -1, -1, 0, 51, 60),
        ("engine.decode", 31, 30, -1, 0, 51.5, 52),
        ("engine.token_wait", 32, 30, -1, 0, 52, 58),
        ("engine.emit", 33, 30, -1, 0, 58, 60),
        ("engine.run", 40, -1, -1, 0, 61, 70),
        ("engine.decode", 41, 40, -1, 0, 62, 63),
        ("engine.token_wait", 42, 40, -1, 0, 63, 69),
        ("engine.emit", 43, 40, -1, 0, 69, 70),
        # engine 1: two decode-only steps
        ("engine.run", 50, -1, -1, 1, 55, 60),
        ("engine.decode", 51, 50, -1, 1, 55, 56),
        ("engine.token_wait", 52, 50, -1, 1, 56, 59),
        ("engine.run", 60, -1, -1, 1, 70, 80),
        ("engine.decode", 61, 60, -1, 1, 71, 72),
        ("engine.token_wait", 62, 60, -1, 1, 72, 79),
        ("policy.feedback", 70, -1, 0, -1, 100, 100.1),
        # after the window: not counted
        ("policy.assign", 80, -1, 9, -1, 2000, 2005),
        ("server.submit", 81, -1, 9, -1, 2000, 2000),
        ("server.dispatch", 82, -1, 9, 0, 2100, 2100),
        ("engine.run", 83, -1, -1, 0, 2200, 2300),
    ]
    rec = D.Record()
    rec.window = (0.0, 1.0)
    rec.routed = 2
    rec.program = _table(rows)
    return rec


def test_policy_us_per_request():
    assert PS.policy_us_per_request(_record()) == \
        pytest.approx((300 + 200 + 100) / 2)


def test_server_wait_ms():
    assert PS.server_wait_ms(_record()) == pytest.approx((5 + 4) / 2)


def test_engine_queue_ms():
    assert PS.engine_queue_ms(_record()) == pytest.approx((5 + 14) / 2)


def test_admit_host_ms():
    assert PS.admit_host_ms(_record()) == pytest.approx((8 + 5) / 2)


def test_decode_gap_ms():
    # engine 0: 51.5 - 48 and 62 - 58 (the step after an admission has no
    # decode-only predecessor); engine 1: 71 - 59
    assert PS.decode_gap_ms(_record()) == pytest.approx((3.5 + 4 + 12) / 3)


def test_longest_run_line():
    line = PS.longest_run(_record())
    assert line.startswith("longest engine.run 22.0 ms on engine 0 at 0.02 s")
    assert "engine.admission 16.0" in line and "engine.first_token 3.0" in line


@pytest.mark.parametrize("fn", [PS.policy_us_per_request, PS.server_wait_ms,
                                PS.engine_queue_ms, PS.admit_host_ms,
                                PS.decode_gap_ms, PS.longest_run])
def test_readers_without_program_spans(fn):
    # the harness's Record before it carried spans, and an empty table
    rec = D.Record()
    rec.window, rec.routed = (0.0, 1.0), 2
    assert fn(rec) is None
    rec.program = None
    assert fn(rec) is None
    rec.program = _table([("engine.emit", 0, -1, -1, 0, 5000, 5001)])
    assert fn(rec) is None


# ---------------------------------------------------------------------------
# a tiny fleet on the CPU, with a recorder attached after the warm-up
# ---------------------------------------------------------------------------

def test_tiny_fleet_readers_finite_and_consistent():
    from chipbench import fleet as F
    from chipbench import spec as S
    from chipbench import traffic as TR
    from repro.obs import TraceRecorder

    cfg = json.loads((DATA / "tiny-config.json").read_text())
    mix = json.loads((DATA / "tiny-chat.json").read_text())
    fleet = F.build(cfg, SEED)
    vocab = min(m.vocab for m in fleet.shapes.values())
    window = TR.generate(mix, vocab, SEED, 1.5)
    D.warm_engines(fleet, [window], SEED)
    rec = D.Record()
    spans = D.Spans(rec)
    spans.wrap_engines(fleet)
    recorder = TraceRecorder()
    fleet.server.attach_trace(recorder)
    t0 = time.time() + 0.05
    served, handles = D.open_loop(fleet, window, t0, 1.5, spans)
    rec.window = (t0, t0 + 1.5)
    rec.routed = sum(1 for r in served if r.submitted >= 0)
    rec.program = recorder.spans()
    assert recorder.spans_dropped == 0
    got = {fn.__name__: fn(rec) for fn in (
        PS.policy_us_per_request, PS.server_wait_ms, PS.engine_queue_ms,
        PS.admit_host_ms, PS.decode_gap_ms)}
    for name, v in got.items():
        assert v is not None and math.isfinite(v) and v >= 0, (name, v)
    runtime = S.metric_reader("runtime_us_per_request")(rec)
    assert 0 < got["policy_us_per_request"] <= runtime
    waits = [sr.engine_req.first_token_at - sr.engine_req.submitted_at
             for sr in handles if sr is not None and sr.engine_req is not None
             and sr.engine_req.first_token_at >= 0]
    assert got["engine_queue_ms"] < 1e3 * sum(waits) / len(waits)
    assert PS.longest_run(rec).startswith("longest engine.run ")
