"""Per-layer quantities read from the program's own spans.

The program records measured-clock spans and marks when a
`repro.obs.TraceRecorder` is attached to the server
(`PerLLMServer.attach_trace`); `TraceRecorder.spans()` gives them as
numpy columns `name, span, parent, sid, server, t0_ns, t1_ns, value`, on
`time.time_ns`, the clock of the benchmark's own stamps. Each function
here takes a run's `Record` carrying that table as `rec.program` and
returns None where there is nothing to read: no table (a program that
records no spans, or a run that attached no recorder), or no span of the
kind it reads inside the window. A request or a span belongs to the
window when it begins in it (`rec.window`, seconds).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional


def _rows(rec) -> Optional[List[tuple]]:
    sp = getattr(rec, "program", None)
    if sp is None or not len(sp["name"]):
        return None
    cols = [sp[k].tolist() for k in ("name", "span", "parent", "sid",
                                     "server", "t0_ns", "t1_ns")]
    return list(zip(*cols))


def _inside(rec, t0_ns: int) -> bool:
    lo, hi = rec.window
    return lo <= t0_ns * 1e-9 < hi


def _children(rows) -> Dict[int, List[tuple]]:
    out: Dict[int, List[tuple]] = defaultdict(list)
    for r in rows:
        out[r[2]].append(r)
    return out


def _mean(xs) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def policy_us_per_request(rec) -> Optional[float]:
    """Microseconds in `policy.assign` and `policy.feedback` over the
    window, per request routed in it."""
    rows = _rows(rec)
    if rows is None or not rec.routed:
        return None
    ns = [r[6] - r[5] for r in rows
          if r[0] in ("policy.assign", "policy.feedback")
          and _inside(rec, r[5])]
    return 1e-3 * sum(ns) / rec.routed if ns else None


def _first_marks(rows, name: str) -> Dict[int, int]:
    out: Dict[int, int] = {}
    for r in rows:
        if r[0] == name and r[3] not in out:
            out[r[3]] = r[5]
    return out


def server_wait_ms(rec) -> Optional[float]:
    """Mean, over the window's requests, of the time from entering the
    server (`server.submit`) to entering an engine (`server.dispatch`):
    routing, the modelled uplink and the event loop's order."""
    rows = _rows(rec)
    if rows is None:
        return None
    sub = _first_marks(rows, "server.submit")
    disp = _first_marks(rows, "server.dispatch")
    return _mean([1e-6 * (disp[s] - t) for s, t in sub.items()
                  if s in disp and _inside(rec, t)])


def engine_queue_ms(rec) -> Optional[float]:
    """Mean, over the window's requests, of the time from entering an
    engine (`server.dispatch`) to the start of its `engine.admission`."""
    rows = _rows(rec)
    if rows is None:
        return None
    sub = _first_marks(rows, "server.submit")
    disp = _first_marks(rows, "server.dispatch")
    adm: Dict[int, int] = {}
    for r in rows:
        s = r[3]
        if r[0] == "engine.admission" and s in disp and s not in adm \
                and r[5] >= disp[s]:
            adm[s] = r[5]
    return _mean([1e-6 * (adm[s] - disp[s]) for s, t in sub.items()
                  if s in adm and _inside(rec, t)])


def admit_host_ms(rec) -> Optional[float]:
    """Mean duration of the window's `engine.admission` spans less their
    `engine.first_token` child (the host blocking on the device for the
    first token): host work while every lane of the engine waits."""
    rows = _rows(rec)
    if rows is None:
        return None
    kids = _children(rows)
    out = []
    for r in rows:
        if r[0] == "engine.admission" and _inside(rec, r[5]):
            ft = sum(c[6] - c[5] for c in kids.get(r[1], ())
                     if c[0] == "engine.first_token")
            out.append(1e-6 * (r[6] - r[5] - ft))
    return _mean(out)


def decode_gap_ms(rec) -> Optional[float]:
    """Mean, over consecutive decode-only `engine.run`s of one engine in
    the window, of the time from the end of one's `engine.token_wait`
    (the sampled tokens reached the host) to the start of the next one's
    `engine.decode`: the host's round trip per decode step."""
    rows = _rows(rec)
    if rows is None:
        return None
    kids = _children(rows)
    runs: Dict[int, List[tuple]] = defaultdict(list)
    for r in rows:
        if r[0] == "engine.run" and _inside(rec, r[5]):
            runs[r[4]].append(r)
    gaps = []
    for seq in runs.values():
        seq.sort(key=lambda r: r[5])
        prev_wait = None
        for r in seq:
            named = {c[0]: c for c in kids.get(r[1], ())}
            decode_only = "engine.decode" in named \
                and "engine.admission" not in named
            if decode_only and prev_wait is not None:
                gaps.append(1e-6 * (named["engine.decode"][5] - prev_wait))
            prev_wait = named["engine.token_wait"][6] if decode_only \
                and "engine.token_wait" in named else None
    return _mean(gaps)


def longest_run(rec) -> Optional[str]:
    """The window's longest `engine.run`, with the milliseconds of the
    spans inside it summed by name: one line for a log."""
    rows = _rows(rec)
    if rows is None:
        return None
    runs = [r for r in rows if r[0] == "engine.run" and _inside(rec, r[5])]
    if not runs:
        return None
    top = max(runs, key=lambda r: r[6] - r[5])
    kids = _children(rows)
    by_name: Dict[str, float] = defaultdict(float)
    stack = [top[1]]
    while stack:
        for c in kids.get(stack.pop(), ()):
            by_name[c[0]] += 1e-6 * (c[6] - c[5])
            stack.append(c[1])
    parts = ", ".join(f"{n} {ms:.1f}" for n, ms in
                      sorted(by_name.items(), key=lambda x: -x[1]))
    return (f"longest engine.run {1e-6 * (top[6] - top[5]):.1f} ms on "
            f"engine {top[4]} at {top[5] * 1e-9 - rec.window[0]:.2f} s "
            f"({parts} ms)")
