"""Drive the fleet from the benchmark's side: open-loop arrivals, token
stamps on the wall clock, and spans around each call into a layer.

Spans are recorded by the benchmark around the calls it makes or wraps:
`PerLLMServer.step` (`bench.step`), the `ServingEngine.step` each engine
tick runs inside it (`engine.step#<i>`, classified after the call as an
admission or a decode-only step from the engine's own counters), the
sleep to the next due time (`bench.wait`) and the token stamping
(`bench.stamp`). With `annotate` on they also go into the profiler's
trace as `TraceAnnotation`s, on the trace's clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench.stats import Served, drained
from chipbench.traffic import Requests


@dataclasses.dataclass
class EngineStep:
    engine: int
    t0: float
    t1: float
    admitted: int          # prompts admitted (prefills and prefix hits)
    ctx_lens: List[int]    # keys each lane active before the step attends


@dataclasses.dataclass
class ServerStep:
    t0: float
    t1: float
    engine_s: float        # of which inside ServingEngine.step


@dataclasses.dataclass
class Record:
    """Everything a run measured; per-layer metric readers read this."""

    served: List[Served] = dataclasses.field(default_factory=list)
    server_steps: List[ServerStep] = dataclasses.field(default_factory=list)
    engine_steps: List[EngineStep] = dataclasses.field(default_factory=list)
    window: tuple = (0.0, 0.0)
    routed: int = 0                    # requests submitted in the window
    engine_waits: List[float] = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    trace: Optional[Dict[str, Any]] = None   # reduced device trace
    shapes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    replica_model: List[str] = dataclasses.field(default_factory=list)
    peaks: Dict[str, float] = dataclasses.field(default_factory=dict)

    def in_window(self, steps):
        lo, hi = self.window
        return [s for s in steps if lo <= s.t0 < hi]


class Spans:
    """Timed calls, optionally mirrored into the profiler's trace."""

    def __init__(self, rec: Record):
        self.rec = rec
        self.annotate = False
        self._engine_s = 0.0

    def mark(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def wrap_engines(self, fleet) -> None:
        """Route each engine's `step` through a timer. The server calls
        `eng.step()` on the instance, so it finds the wrapper."""
        for j, eng in enumerate(fleet.engines):
            eng.step = self._wrapped(j, eng, eng.step, fleet.server)

    def _wrapped(self, j, eng, step, server):
        def timed_step():
            ctx = [len(sr.engine_req.prompt) + len(sr.engine_req.generated)
                   for sr in server.active.values()
                   if sr.server == j and sr.engine_req is not None
                   and sr.engine_req.slot >= 0]
            n0 = eng.n_prefills + eng.n_prefix_hits
            i = len(self.rec.engine_steps)
            t0 = time.time()
            with self.mark(f"engine.step#{i}"):
                n = step()
            t1 = time.time()
            self.rec.engine_steps.append(EngineStep(
                engine=j, t0=t0, t1=t1,
                admitted=eng.n_prefills + eng.n_prefix_hits - n0,
                ctx_lens=ctx))
            self._engine_s += t1 - t0
            return n
        return timed_step

    def server_step(self, server) -> None:
        self._engine_s = 0.0
        t0 = time.time()
        with self.mark("bench.step"):
            server.step()
        t1 = time.time()
        self.rec.server_steps.append(ServerStep(t0, t1, self._engine_s))

    def wait_until(self, t: float) -> None:
        dt = t - time.time()
        if dt > 0:
            with self.mark("bench.wait"):
                time.sleep(dt)


class Tracker:
    """Stamps each new output token of each in-flight request after a
    server step, on the engine's clock (`time.time`)."""

    def __init__(self, server):
        self.server = server
        self.inflight: Dict[int, tuple] = {}   # index -> (ServedRequest, Served)
        self._rejected_seen = 0

    def add(self, i: int, sr, rec: Served) -> None:
        self.inflight[i] = (sr, rec)

    def stamp(self) -> None:
        now = time.time()
        rej = self.server.rejected[self._rejected_seen:]
        self._rejected_seen = len(self.server.rejected)
        rejected = {id(sr) for sr in rej}
        for i, (sr, rec) in list(self.inflight.items()):
            if id(sr) in rejected:
                rec.failed = True
                del self.inflight[i]
                continue
            er = sr.engine_req
            if er is None:
                continue
            have, n = len(rec.stamps), len(er.generated)
            if n > have:
                if have == 0:
                    rec.stamps.append(er.first_token_at)
                    have = 1
                rec.stamps.extend([now] * (n - have))
            if er.done:
                rec.finished = True
                del self.inflight[i]


def open_loop(fleet, reqs: Requests, t_start: float, seconds: float,
              spans: Spans, on_tick=None):
    """Submit each request when due, step the server while its event
    loop holds work, sleep to the next due time when it does not; after
    the window, drain until every request finished, failed or passed its
    deadline. Returns the requests' `Served` records and their server
    handles."""
    server = fleet.server
    n = len(reqs)
    served = [Served(due=t_start + float(reqs.due[i]),
                     deadline=float(reqs.deadline[i])) for i in range(n)]
    handles: List[Any] = [None] * n
    tracker = Tracker(server)
    t_end = t_start + seconds
    hard_stop = t_end + float(np.max(reqs.deadline, initial=0)) + 5.0
    i = 0
    while True:
        now = time.time()
        while i < n and served[i].due <= now:
            handles[i] = server.submit(
                reqs.prompts[i], max_new_tokens=int(reqs.max_new[i]),
                deadline=float(reqs.deadline[i]),
                payload_bytes=float(reqs.payload[i]))
            served[i].submitted = time.time()
            tracker.add(i, handles[i], served[i])
            i += 1
        if on_tick is not None:
            on_tick(now)
        if server.loop:
            spans.server_step(server)
            with spans.mark("bench.stamp"):
                tracker.stamp()
        elif i < n:
            spans.wait_until(served[i].due)
        else:
            break
        now = time.time()
        if i == n and now >= t_end and drained(served, now):
            break
        if now > hard_stop:
            break
    return served, handles


def warm_engines(fleet, reqs_list: List[Requests], vocab_seed: int) -> int:
    """Compile, on every engine, each shape the given traffic will use:
    one request per distinct prompt length in 16-token blocks (prefill
    bucket, page scatter), with two output tokens (decode, sampling).
    Returns the number of warm-up requests."""
    from chipbench.traffic import rng
    blocks = {}
    for reqs in reqs_list:
        for p in reqs.prompt_lens.tolist():
            blocks.setdefault(p // 16, p)
    lens = sorted(blocks.values())
    g = rng(vocab_seed, 0x3A12)
    total = 0
    for eng in fleet.engines:
        vocab = eng.cfg.vocab_size
        for p in lens:
            eng.submit(g.integers(0, vocab, p).tolist(), max_new_tokens=2)
            total += 1
        eng.run_until_idle()
    return total
