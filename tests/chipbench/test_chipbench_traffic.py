"""The traffic generator: deterministic per seed, the same work for every
seed, and the paper's marginals."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic as T

MIX = json.loads((Path(__file__).parents[2] / "benchmarks" / "chip"
                  / "traffic" / "paper-chat.json").read_text())
BIG_SEED = 2**31 + 977


def test_same_seed_same_requests():
    a = T.generate(MIX, 64000, BIG_SEED, 30.0)
    b = T.generate(MIX, 64000, BIG_SEED, 30.0)
    assert np.array_equal(a.due, b.due)
    assert a.prompts == b.prompts
    assert np.array_equal(a.max_new, b.max_new)
    assert np.array_equal(a.deadline, b.deadline)


def test_seeds_share_arrivals_and_sizes():
    a = T.generate(MIX, 64000, 11, 30.0)
    b = T.generate(MIX, 64000, 12, 30.0)
    assert a.prompts != b.prompts
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.max_new, b.max_new)
    assert np.array_equal(a.deadline, b.deadline)
    assert np.array_equal(a.payload, b.payload)
    assert a.due[-1] <= 30.0


def test_payload_follows_prompt_length():
    r = T.generate(MIX, 64000, BIG_SEED, 30.0)
    per_token = MIX["payload_bytes"]["bytes_per_token"]
    assert np.array_equal(r.payload, r.prompt_lens * per_token)
    uniform = dict(MIX, payload_bytes={"dist": "uniform", "low": 2e3,
                                       "high": 16e3})
    u = T.generate(uniform, 64000, BIG_SEED, 30.0)
    assert u.payload.min() >= 2e3 and u.payload.max() <= 16e3
    assert np.array_equal(u.prompt_lens, r.prompt_lens)


def test_streams_are_independent():
    a = T.generate(MIX, 64000, 5, 10.0, stream=0)
    b = T.generate(MIX, 64000, 5, 10.0, stream=1)
    assert a.prompts != b.prompts


def test_marginals():
    mix = dict(MIX, rate_rps=2000.0)
    r = T.generate(mix, 64000, 3, 10.0)
    n = len(r)
    assert n == 20000
    p = r.prompt_lens
    assert p.min() >= 32 and p.max() <= 2048
    # lognormal(5.0, 0.8) clipped: median exp(5) ~ 148
    assert np.median(p) == pytest.approx(np.exp(5.0), rel=0.05)
    assert r.max_new.min() >= 4 and r.max_new.max() <= 96
    assert np.median(r.max_new) == pytest.approx(np.exp(2.8), rel=0.08)
    assert r.deadline.min() >= 2.0 and r.deadline.max() <= 6.0
    assert r.deadline.mean() == pytest.approx(4.0, rel=0.02)
    # the prompt's text, 4 bytes a token
    assert r.payload.min() >= 4 * 32 and r.payload.max() <= 4 * 2048
    gaps = np.diff(np.r_[0, r.due])
    assert gaps.mean() == pytest.approx(1 / 2000.0, rel=0.05)
    # exponential gaps: the standard deviation equals the mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.05)
    tok = np.concatenate([np.array(x) for x in r.prompts[:200]])
    assert tok.min() >= 0 and tok.max() < 64000


def test_no_shared_prefix_blocks():
    r = T.generate(MIX, 64000, 9, 40.0)
    heads = {tuple(p[:16]) for p in r.prompts}
    assert len(heads) == len(r)


def test_request_count_follows_rate_and_window():
    assert T.n_requests(MIX, 40.0) == round(MIX["rate_rps"] * 40.0)
    assert len(T.generate(MIX, 100, 1, 40.0)) == T.n_requests(MIX, 40.0)


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError):
        T.generate(dict(MIX, kind="nope"), 100, 1, 1.0)
