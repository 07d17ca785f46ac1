"""Arithmetic of the end-to-end metrics, on wall-clock stamps.

A request is timed from when it was due, not from when it was submitted,
so a late submission counts against the system. A request that was
refused, failed, or was unfinished when the drain ended misses every
limit: its time to first token is infinite when no token came, and it
never meets its deadline.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, List, Sequence

INF = float("inf")


def percentile(values: Sequence[float], q: float) -> float:
    """The nearest-rank `q`-th percentile (0 < q <= 100): the smallest
    value with at least q% of the sample at or below it. Infinite values
    rank above every finite one; an empty sample reads infinite."""
    if not values:
        return INF
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


@dataclasses.dataclass
class Served:
    """One request due in the window, as the benchmark saw it."""

    due: float                  # wall time it was due
    deadline: float             # seconds after `due`
    submitted: float = -1.0     # wall time it was submitted
    stamps: List[float] = dataclasses.field(default_factory=list)
    finished: bool = False      # all its tokens came
    failed: bool = False        # refused or lost by the system

    @property
    def ttft(self) -> float:
        return self.stamps[0] - self.due if self.stamps else INF

    @property
    def gaps(self) -> List[float]:
        s = self.stamps
        return [b - a for a, b in zip(s[:-1], s[1:])]

    @property
    def met_deadline(self) -> bool:
        return (self.finished and not self.failed
                and self.stamps[-1] - self.due <= self.deadline)


def ttft_p95_ms(reqs: Iterable[Served]) -> float:
    return 1e3 * percentile([r.ttft for r in reqs], 95)


def itl_p95_ms(reqs: Iterable[Served]) -> float:
    return 1e3 * percentile([g for r in reqs for g in r.gaps], 95)


def deadline_met_pct(reqs: Sequence[Served]) -> float:
    if not reqs:
        return 0.0
    return 100.0 * sum(r.met_deadline for r in reqs) / len(reqs)


def tokens_per_s(reqs: Iterable[Served], t0: float, t1: float) -> float:
    """Output tokens stamped inside [t0, t1), over the window's length."""
    n = sum(1 for r in reqs for s in r.stamps if t0 <= s < t1)
    return n / (t1 - t0)


def lateness_p99_ms(reqs: Iterable[Served]) -> float:
    """How late the generator submitted (p99), in ms."""
    late = [max(r.submitted - r.due, 0.0) for r in reqs if r.submitted >= 0]
    return 1e3 * percentile(late, 99) if late else 0.0


def drained(reqs: Iterable[Served], now: float) -> bool:
    """The drain is over: every request finished, failed, or is past its
    deadline."""
    return all(r.finished or r.failed or now > r.due + r.deadline
               for r in reqs)

