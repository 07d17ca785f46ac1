#!/usr/bin/env python3
"""On-chip benchmark of the PerLLM serving path.

    python3 benchmarks/chip/run.py --workload NAME --seed N --seconds S \
        --trace 0|1

Runs one cell of `BENCHMARK.json` (a configuration under a traffic mix)
on the chips of the machine it is started on, through the entry users
drive: `PerLLMServer.submit` / `PerLLMServer.step` over paged
`ServingEngine`s. Set-up (weights from the seed, compiling and warming
every shape the window's traffic uses) is timed as `setup_s`; then the
window offers the mix open loop for S seconds and drains it. With
`--trace 0` the last line of standard output holds the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics, read partly
from a profiler trace of the window's last seconds. Every run then
checks the served tokens against a plain float32 reference and reports
`correct`, with each number compared and its limit last.

    --control 1     judge the float8 control in the program's place:
                    the tokens compared are those the control puts first
                    at each served position, so `correct` comes out
                    false (calibration; the cell's own runs never set it)
    --sweep R1,R2   one process, one set-up, a window at each rate:
                    prints the deadline share and the backlog per rate

With `CHIPBENCH_DUMP_TRACE=FILE.json.gz` a traced run also writes the
trace's normalised events there (how the test fixture was recorded).

Without a TPU, or with fewer chips than the cell needs, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench.spec import CHECKOUT  # noqa: E402

TRACE_TAIL_S = 3.0       # the traced part: the window's last seconds
WARM_MIX_S = 3.0         # untimed warm-up of the cell's own mix
LISTENED = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_jax(cache: bool = True):
    """The persistent compile cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), holding every program,
    the small eager ones too."""
    import jax
    if not cache:
        return jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def device_info(jax, chips: int, require_tpu: bool = True):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices; "
                         "this benchmark runs only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class GcTimer:
    """Garbage collections of the interpreter while `on`: how many, and
    the longest, in seconds."""

    def __init__(self):
        self.on = False
        self.n = 0
        self.longest = 0.0
        self._t = 0.0
        gc.callbacks.append(self._hear)

    def _hear(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.n += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._hear)


class CompileCounter:
    """Counts programs compiled (or loaded from the persistent cache)
    while `on`."""

    def __init__(self, jax):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event == LISTENED:
            self.n += 1


def backlog_at(served, t: float) -> int:
    return sum(1 for r in served if r.submitted >= 0 and r.submitted <= t
               and not r.failed
               and not (r.finished and r.stamps and r.stamps[-1] <= t))


def _done_requests(handles, fleet):
    from chipbench.check import Done
    out = []
    for sr in handles:
        if sr is None or sr.engine_req is None or not sr.engine_req.done:
            continue
        er = sr.engine_req
        out.append(Done(prompt=list(er.prompt), tokens=list(er.generated),
                        model=fleet.replica_model[sr.server]))
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool,
             control: bool = False, require_tpu: bool = True,
             devices=None, t_start: float = T_START, out=print):
    """One run of `cell`; returns the result dict it prints."""
    jax = configure_jax(cache=require_tpu)
    info = device_info(jax, cell.chips, require_tpu)

    from chipbench import check as C
    from chipbench import drive as D
    from chipbench import fleet as F
    from chipbench import peaks as P
    from chipbench import spec as S
    from chipbench import stats as ST
    from chipbench import traffic as TR
    from chipbench import weights as W

    cfg = cell.config
    counter = CompileCounter(jax)
    fleet = F.build(cfg, seed, devices=devices)
    vocab = min(m.vocab for m in fleet.shapes.values())
    window = TR.generate(cell.traffic, vocab, seed, seconds)
    warm_mix = TR.generate(cell.traffic, vocab, seed, WARM_MIX_S, stream=1)
    n_warm = D.warm_engines(fleet, [window, warm_mix], seed)
    rec = D.Record(shapes=fleet.shapes, replica_model=fleet.replica_model,
                   peaks=P.peaks(info["kind"]) if require_tpu else {})
    spans = D.Spans(rec)
    spans.wrap_engines(fleet)
    D.open_loop(fleet, warm_mix, time.time(), WARM_MIX_S, spans)
    rec.server_steps.clear()
    rec.engine_steps.clear()
    # set-up's objects are kept for good: the collector need not walk them
    gc.collect()
    gc.freeze()
    gc_timer = GcTimer()

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    t0 = time.time() + 0.05
    setup_s = t0 - t_start
    tracing = {"on": False}

    def on_tick(now):
        # the annotations end with the window, which ends the traced
        # part; the profiler stops after the drain, as stopping it takes
        # seconds in which no request would be served
        if not trace:
            return
        if tracing["on"] is False and now >= t0 + seconds - TRACE_TAIL_S \
                and now < t0 + seconds:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # Python calls: far too many
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing["on"] = True
            spans.annotate = True
        elif tracing["on"] and now >= t0 + seconds:
            spans.annotate = False

    counter.on = gc_timer.on = True
    served, handles = D.open_loop(fleet, window, t0, seconds, spans,
                                  on_tick=on_tick)
    counter.on = False
    gc_timer.close()
    t_end = time.time()
    if tracing["on"]:
        spans.annotate = False
        jax.profiler.stop_trace()
    rec.served = served
    rec.window = (t0, t0 + seconds)
    rec.routed = sum(1 for r in served if r.submitted >= 0)
    rec.compiles_in_window = counter.n
    rec.engine_waits = [sr.engine_req.first_token_at
                        - sr.engine_req.submitted_at for sr in handles
                        if sr is not None and sr.engine_req is not None
                        and sr.engine_req.first_token_at >= 0]
    mem = [d.memory_stats() or {} for d in fleet.devices]
    info["memory_peak_bytes"] = int(max(
        m.get("peak_bytes_in_use", 0) for m in mem))
    late = ST.lateness_p99_ms(served)
    failed = sum(1 for r in served if r.failed or not r.finished)
    log(f"window: {len(served)} requests due in {seconds:.0f} s, "
        f"{rec.routed} submitted, submit_late_p99_ms={late:.3f}, "
        f"compiles_in_window={counter.n}, warm-up requests={n_warm}, "
        f"drain_s={t_end - t0 - seconds:.2f}, failed={failed}")
    per_server = [sum(1 for sr in handles if sr is not None
                      and sr.server == j) for j in range(len(fleet.engines))]
    log(f"routing: requests per replica {per_server}")
    steps = rec.in_window(rec.server_steps)
    longest = max(steps, key=lambda s: s.t1 - s.t0, default=None)
    if longest is not None:
        log(f"host: longest server step {1e3 * (longest.t1 - longest.t0):.1f}"
            f" ms ({1e3 * longest.engine_s:.1f} ms in engine steps) at "
            f"{longest.t0 - t0:.2f} s; {gc_timer.n} garbage collections, "
            f"longest {1e3 * gc_timer.longest:.1f} ms")

    # ---- metrics
    metrics = {}
    e2e = {
        "ttft_p95_ms": lambda: ST.ttft_p95_ms(served),
        "itl_p95_ms": lambda: ST.itl_p95_ms(served),
        "deadline_met_pct": lambda: ST.deadline_met_pct(served),
        "setup_s": lambda: setup_s,
    }
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]()),
                                  "unit": m["unit"]}
    done = _done_requests(handles, fleet)
    mismatched = C.routing_mismatches(handles, fleet.engines)
    dev_planes = [f"/device:{d.platform.upper()}:{d.id}"
                  for d in fleet.devices]
    model_of = dict(fleet.shapes)

    # ---- free the program before the trace and the reference
    del handles, fleet, spans
    gc.collect()

    breakdown = None
    if trace:
        from chipbench import trace as TRC
        kinds = {i: ("engine.admit" if s.admitted else "engine.decode")
                 for i, s in enumerate(rec.engine_steps)}
        t_load = time.time()
        tr = TRC.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        planes = list(dict.fromkeys(dev_planes))
        tr["devices"] = {p: tr["devices"].get(p, []) for p in planes}
        tr["modules"] = {p: tr["modules"].get(p, []) for p in planes}
        log(f"trace: read in {time.time() - t_load:.1f} s")
        log("trace: " + ", ".join(f"{k}: {len(v)} device events"
                                  for k, v in tr["devices"].items())
            + f"; {len(tr['host'])} host spans")
        if os.environ.get("CHIPBENCH_DUMP_TRACE"):
            import gzip
            with gzip.open(os.environ["CHIPBENCH_DUMP_TRACE"], "wt") as f:
                json.dump({"kinds": kinds, "trace": tr}, f)
        red = TRC.reduce(tr, kinds)
        used = [p for p in dict.fromkeys(dev_planes) if p in tr["devices"]]
        red["busy_frac"] = [red["busy_ns"][p] / red["window_ns"]
                            for p in used]
        dec = []
        for i, (a, b) in red["steps"].items():
            st = rec.engine_steps[i]
            if st.admitted or not st.ctx_lens:
                continue
            dec.append((i, a, b))
        from chipbench import flops as FL
        least, dev_ns = [], []
        for p_idx, plane in enumerate(dict.fromkeys(dev_planes)):
            mine = [(i, a, b) for i, a, b in dec
                    if dev_planes[rec.engine_steps[i].engine] == plane]
            ns = TRC.spans_device_ns(tr, plane, [(a, b) for _, a, b in mine])
            for (i, _, _), t in zip(mine, ns, strict=True):
                st = rec.engine_steps[i]
                m = model_of[rec.replica_model[st.engine]]
                least.append(FL.decode_step_least_s(
                    m, st.ctx_lens, rec.peaks["bf16_flops"],
                    rec.peaks["hbm_bytes_per_s"]))
                dev_ns.append(t)
        red["decode_least_s"] = least
        red["decode_device_s"] = [t * 1e-9 for t in dev_ns]
        rec.trace = red
        info["busy_s"] = (sum(red["busy_ns"][p] for p in used)
                          / max(len(used), 1) * 1e-9)
        info["window_s"] = red["window_ns"] * 1e-9
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        for m in cell.per_layer:
            v = S.metric_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---- correctness: the reference over what the window served
    checks = {}
    ok = True
    pack = int(cfg["check"]["pack_tokens"])
    ref = S.reference(cfg["check"]["reference"])
    t_ref = time.time()
    compared = 0
    for name, m in model_of.items():
        mine = [d for d in done if d.model == name]
        if not mine:
            log(f"check: no request of {name} finished in the window")
            continue
        pick = C.sample(mine, seed, pack)
        v = C.compare(ref, m, W.model_key(seed, name), pick, pack,
                      control=control)
        lim = float(cfg["check"]["max_logit_gap"][name])
        gap = v.control_gap if control else v.gap
        checks[f"logit_gap.{name}"] = (gap, lim)
        ok = ok and gap <= lim
        compared += v.tokens
        log(f"check: {name}: {v.requests} requests, {v.tokens} served "
            f"tokens, longest {max(d.length for d in pick)} tokens")
        if control:
            log(f"control: {name}: the float8 control in the program's "
                f"place reads gap={v.control_gap!r}; the program's served "
                f"tokens read gap={v.gap!r}")
    if compared == 0:
        ok = False
    checks["served_tokens_compared"] = (compared, 1)
    ok = ok and compared >= 1
    checks["routing_mismatches"] = (mismatched, 0)
    ok = ok and mismatched == 0
    log(f"check: reference took {time.time() - t_ref:.1f} s")
    report = C.limits_report(checks)
    for k, v in report.items():
        cmp = ">=" if k == "served_tokens_compared" else "<="
        log(f"{k}={v['value']!r} limit {cmp} {v['limit']!r}")
    result = {"correct": bool(ok), "attempted": len(served),
              "failed": failed, "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = report
    out(json.dumps(result))
    return result


def sweep(cell, seed: int, seconds: float, rates):
    """One set-up, then a window at each rate: deadline share and backlog
    at the window's middle and end."""
    jax = configure_jax()
    device_info(jax, cell.chips)
    from chipbench import drive as D
    from chipbench import fleet as F
    from chipbench import stats as ST
    from chipbench import traffic as TR
    fleet = F.build(cell.config, seed)
    vocab = min(m.vocab for m in fleet.shapes.values())
    mixes = [dict(cell.traffic, rate_rps=r) for r in rates]
    wins = [TR.generate(mx, vocab, seed + k, seconds)
            for k, mx in enumerate(mixes)]
    warm = TR.generate(mixes[0], vocab, seed, WARM_MIX_S, stream=1)
    D.warm_engines(fleet, wins + [warm], seed)
    spans = D.Spans(D.Record())
    spans.wrap_engines(fleet)
    D.open_loop(fleet, warm, time.time(), WARM_MIX_S, spans)
    log(f"sweep: set-up {time.time() - T_START:.1f} s")
    for r, win in zip(rates, wins, strict=True):
        t0 = time.time() + 0.05
        served, _ = D.open_loop(fleet, win, t0, seconds, spans)
        mid = backlog_at(served, t0 + seconds / 2)
        end = backlog_at(served, t0 + seconds)
        print(json.dumps({
            "rate_rps": r, "requests": len(served),
            "deadline_met_pct": ST.deadline_met_pct(served),
            "ttft_p95_ms": ST.ttft_p95_ms(served),
            "itl_p95_ms": ST.itl_p95_ms(served),
            "output_tok_s": ST.tokens_per_s(served, t0, t0 + seconds),
            "backlog_mid": mid, "backlog_end": end,
            "submit_late_p99_ms": ST.lateness_p99_ms(served)}), flush=True)
        # let the fleet empty before the next rate
        while fleet.server.loop:
            fleet.server.step()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    from chipbench.spec import load_cell
    cell = load_cell(args.workload)
    if args.sweep:
        sweep(cell, args.seed, args.seconds,
              [float(x) for x in args.sweep.split(",")])
        return 0
    run_cell(cell, args.seed, args.seconds, bool(args.trace),
             control=bool(args.control))
    return 0


if __name__ == "__main__":
    sys.exit(main())
