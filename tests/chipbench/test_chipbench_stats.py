"""Percentile, rate and drain arithmetic on hand-made stamps."""
import math

import pytest

from chipbench import stats as S


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert S.percentile(xs, 95) == 95
    assert S.percentile(xs, 100) == 100
    assert S.percentile([3.0], 95) == 3.0
    assert S.percentile([5, 1, 3, 2, 4], 50) == 3
    assert math.isinf(S.percentile([], 95))


def test_missing_requests_rank_above_every_latency():
    xs = [0.1] * 95 + [math.inf] * 5
    assert S.percentile(xs, 95) == 0.1
    assert math.isinf(S.percentile(xs + [math.inf], 95))


def _req(due, deadline, stamps, finished=True, failed=False, sub=None):
    r = S.Served(due=due, deadline=deadline, stamps=list(stamps),
                 finished=finished, failed=failed)
    r.submitted = due if sub is None else sub
    return r


def test_ttft_itl_and_deadlines():
    reqs = [
        _req(10.0, 2.0, [10.2, 10.25, 10.35]),
        _req(11.0, 0.5, [11.1, 11.3, 11.7]),            # late: 0.7 > 0.5
        _req(12.0, 5.0, [], finished=False),              # never started
        _req(13.0, 5.0, [13.4, 13.45], finished=False),   # unfinished
        _req(14.0, 5.0, [], failed=True),                 # refused
    ]
    assert reqs[0].ttft == pytest.approx(0.2)
    assert reqs[0].gaps == pytest.approx([0.05, 0.1])
    assert math.isinf(reqs[2].ttft)
    assert [r.met_deadline for r in reqs] == [True, False, False, False,
                                             False]
    assert S.deadline_met_pct(reqs) == pytest.approx(20.0)
    assert math.isinf(S.ttft_p95_ms(reqs))
    assert S.ttft_p95_ms(reqs[:2]) == pytest.approx(200.0)
    gaps = [0.05, 0.1, 0.2, 0.4, 0.05]
    assert S.itl_p95_ms(reqs) == pytest.approx(1e3 * max(gaps))


def test_tokens_per_s_counts_only_the_window():
    reqs = [_req(0.0, 9.0, [0.5, 1.0, 1.5, 2.5]), _req(1.0, 9.0, [1.9, 2.1])]
    assert S.tokens_per_s(reqs, 1.0, 2.0) == pytest.approx(3.0)


def test_lateness_and_drain():
    reqs = [_req(1.0, 2.0, [1.5], finished=False, sub=1.004),
            _req(2.0, 2.0, [2.1, 2.2], sub=2.001)]
    assert S.lateness_p99_ms(reqs) == pytest.approx(4.0)
    assert not S.drained(reqs, 2.5)
    assert S.drained(reqs, 3.01)
    reqs[0].finished = True
    assert S.drained(reqs, 2.5)

