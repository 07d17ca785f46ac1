"""From a profiler trace to device busy time, idle gaps and the device
time of the benchmark's spans.

`load` reads an `.xplane.pb` into a small normalised form:

    {"host": [[name, start_ns, end_ns], ...],           # benchmark spans
     "devices": {plane: [[start_ns, end_ns, op], ...]},   # device ops
     "modules": {plane: [[start_ns, end_ns, program], ...]}}

Device events are those of each chip plane's "XLA Ops" line (its "XLA
Modules" line where a plane has no op line): the intervals in which an
operation ran. The "XLA Modules" line gives the program each interval
belongs to; the jitted steps are anonymous today, so programs are named
as XLA names them (`jit__lambda`, `jit__prefill_cache`, one per eager
op). Host events are the benchmark's own annotations (`bench.*`,
`engine.*`). Everything after `load` works on that form, so it is
checked on a small recorded trace kept with the tests.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

HOST_PREFIXES = ("bench.", "engine.")
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def _short(name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_chip(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and not plane_name.startswith(
        ("/device:CPU", "/device:CUSTOM"))


def load(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    host, devices, modules = [], {}, {}
    for plane in data.planes:
        if is_chip(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            pick = [n for n in OP_LINES if n in lines] or \
                [n for n in MODULE_LINES if n in lines]
            evs = []
            for n in pick:
                for ev in lines[n].events:
                    s = float(ev.start_ns)
                    evs.append([s, s + float(ev.duration_ns),
                                _short(ev.name)])
            devices[plane.name] = evs
            mods = []
            for n in MODULE_LINES:
                for ev in lines[n].events if n in lines else ():
                    s = float(ev.start_ns)
                    mods.append([s, s + float(ev.duration_ns), ev.name])
            modules[plane.name] = mods
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        s = float(ev.start_ns)
                        host.append([ev.name, s, s + float(ev.duration_ns)])
    return {"host": host, "devices": devices, "modules": modules}


def union(intervals: Sequence[Sequence[float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    xs = sorted((max(a, lo), min(b, hi)) for a, b, *_ in intervals
                if b > lo and a < hi)
    out: List[List[float]] = []
    for a, b in xs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy(intervals, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals inside [lo, hi)."""
    out, t = [], lo
    for a, b in union(intervals, lo, hi):
        if a > t:
            out.append((t, a))
        t = b
    if hi > t:
        out.append((t, hi))
    return out


def host_label(host: Sequence[Sequence], t: float,
               kinds: Optional[Dict[int, str]] = None) -> str:
    """The innermost benchmark span open at `t`; an engine step is named
    by its kind (`engine.admit`, `engine.decode`), a server step with no
    engine step open is routing (`bench.route`). Spans nest, so the
    innermost open span is the shortest."""
    best = None
    for name, a, b in host:
        if a > t:
            break
        if t < b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    if best is None:
        return "bench.other"
    name = best[0]
    if name.startswith("engine.step#"):
        i = int(name.split("#", 1)[1])
        return (kinds or {}).get(i, "engine.step")
    if name == "bench.step":
        return "bench.route"
    return name


def window(host: Sequence[Sequence],
           devices: Dict[str, Sequence[Sequence]]) -> Tuple[float, float]:
    """The traced window: from the first benchmark span or the first
    device event, whichever is later (the device's tracer starts a little
    after the host's), to the end of the last benchmark span."""
    first_dev = [min(e[0] for e in evs) for evs in devices.values() if evs]
    lo = min(a for _, a, _ in host)
    if first_dev:
        lo = max(lo, min(first_dev))
    return lo, max(b for _, _, b in host)


def reduce(tr: Dict, kinds: Dict[int, str], top: int = 10) -> Dict:
    """Busy and idle time per device over the traced window, the engine
    step spans, and the breakdown: the device programs that took most
    time and the longest idle gaps, named by host span."""
    host = sorted(tr["host"], key=lambda h: h[1])
    lo, hi = window(host, tr["devices"])
    per_dev = {}
    ops: Dict[str, float] = {}
    idle = []
    for dev, evs in tr["devices"].items():
        per_dev[dev] = busy(evs, lo, hi)
        for s, e, name in tr.get("modules", {}).get(dev, []):
            name = name.split("(", 1)[0]    # drop the program's fingerprint
            if e > lo and s < hi:
                ops[name] = ops.get(name, 0.0) + min(e, hi) - max(s, lo)
        for a, b in gaps(evs, lo, hi):
            idle.append((b - a, host_label(host, (a + b) / 2, kinds)))
    steps = {}
    for name, a, b in host:
        if name.startswith("engine.step#"):
            i = int(name.split("#", 1)[1])
            steps[i] = (a, b)
    idle.sort(key=lambda x: -x[0])
    return {
        "window_ns": hi - lo,
        "busy_ns": per_dev,
        "steps": steps,
        "device_ops": sorted(([n, t * 1e-9] for n, t in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[name, d * 1e-9] for d, name in idle[:top]],
    }


def spans_device_ns(tr: Dict, dev: str,
                    spans: Sequence[Tuple[float, float]]) -> List[float]:
    """Device busy time on `dev` inside each host span [a, b)."""
    import bisect
    evs = sorted(tr["devices"].get(dev, []))
    starts = [e[0] for e in evs]
    longest = max((e[1] - e[0] for e in evs), default=0.0)
    out = []
    for a, b in spans:
        i = bisect.bisect_left(starts, a - longest)
        j = bisect.bisect_left(starts, b)
        out.append(busy(evs[i:j], a, b))
    return out
