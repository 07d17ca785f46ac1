"""A whole run of the harness on the CPU at a tiny size, with the look
for a chip skipped: sound, it comes out correct; with the timed path
broken underneath, or with the float8 control judged in the program's
place, `correct` comes out false. Also the refusal to run without a
TPU."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench import check as C
from chipbench.spec import Cell

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
E2E = [{"name": n, "unit": u} for n, u in
       (("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"),
        ("deadline_met_pct", "%"), ("setup_s", "s"))]
SEED = 2**31 + 4099


def tiny_cell():
    return Cell("tiny", 1, "tiny",
                json.loads((DATA / "tiny-config.json").read_text()),
                "tiny-chat",
                json.loads((DATA / "tiny-chat.json").read_text()), E2E, [])


def run_tiny(control=False):
    import run
    out = []
    res = run.run_cell(tiny_cell(), SEED, 1.5, False, control=control,
                       require_tpu=False, t_start=time.time(),
                       out=out.append)
    line = json.loads(out[-1])
    assert list(line)[-1] == "checks"
    assert line == json.loads(json.dumps(res))
    return res


def test_sound_run_is_correct_and_control_fails():
    res = run_tiny()
    assert res["correct"] is True
    assert res["attempted"] == 24
    assert set(res["metrics"]) == {m["name"] for m in E2E}
    assert res["device"]["count"] == 1
    checks = res["checks"]
    assert checks["routing_mismatches"]["value"] == 0
    assert checks["served_tokens_compared"]["value"] > 20
    # the float8 control, judged in the program's place, fails the run
    ctrl = run_tiny(control=True)
    assert ctrl["correct"] is False
    for name in ("tiny-a", "tiny-b"):
        served = checks[f"logit_gap.{name}"]["value"]
        gap = ctrl["checks"][f"logit_gap.{name}"]
        assert gap["value"] > gap["limit"]
        assert gap["value"] >= 3 * max(served, 1e-3)


def _patch_decode(monkeypatch, fault):
    from repro.models import model as M
    real = M.decode_step

    def broken(params, tokens, cache, pos, **kw):
        logits, new_cache = real(params, tokens, cache, pos, **kw)
        return fault(logits, new_cache, cache)
    monkeypatch.setattr(M, "decode_step", broken)


def test_fault_state_unchanged(monkeypatch):
    # the decode step returns the cache it was given: no KV is written
    _patch_decode(monkeypatch, lambda lg, new, old: (lg, old))
    assert run_tiny()["correct"] is False


def test_fault_half_the_batch(monkeypatch):
    # lanes of the second half get the first half's logits
    import jax.numpy as jnp

    def half(lg, new, old):
        b = lg.shape[0]
        if b < 2:
            return lg, new
        return jnp.concatenate([lg[: b // 2], lg[: b - b // 2]]), new
    _patch_decode(monkeypatch, half)
    assert run_tiny()["correct"] is False


def test_fault_token_altered(monkeypatch):
    # every sampled token is moved to its neighbour where it is produced
    from repro.serving import engine as E
    real = E.sample_tokens

    def altered(key, logits, temperature=0.0, top_k=0):
        return (real(key, logits, temperature, top_k) + 1) % logits.shape[-1]
    monkeypatch.setattr(E, "sample_tokens", altered)
    assert run_tiny()["correct"] is False


def test_gap_arithmetic():
    ref = [[0.0, 2.0, 1.5], [3.0, 1.0, 2.9]]
    assert list(C.gaps(ref, [1, 0])) == [0.0, 0.0]
    assert list(C.gaps(ref, [2, 2])) == pytest.approx([0.5, 0.1])


def test_sample_and_pack():
    done = [C.Done(prompt=list(range(10 + i)), tokens=[1, 2, 3], model="m")
            for i in range(6)]
    pick = C.sample(done, 3, 40)
    assert pick[0].length == max(d.length for d in done)
    assert sum(d.length for d in pick) <= 40
    assert C.sample(done, 3, 40) == pick
    tokens, pos, seg, rows, targets = C.pack_rows(pick, 40)
    first = pick[0]
    assert list(tokens[:first.length]) == first.prompt + first.tokens[:-1]
    assert pos[first.length] == 0
    assert list(rows[:3]) == [len(first.prompt) - 1 + j for j in range(3)]
    assert list(targets[:3]) == first.tokens


def test_routing_check():
    class Req:
        def __init__(self, done=True):
            self.done = done

    class Dec:
        def __init__(self, s):
            self.server = s

    class SR:
        def __init__(self, server, decided, req):
            self.server, self.decision, self.engine_req = (
                server, Dec(decided), req)

    class Eng:
        def __init__(self, reqs):
            self.completed = reqs
    a, b, c = Req(), Req(), Req(done=False)
    engines = [Eng([a]), Eng([b])]
    assert C.routing_mismatches([SR(0, 0, a), SR(1, 1, b), SR(0, 0, c)],
                                engines) == 0
    assert C.routing_mismatches([SR(0, 0, b)], engines) == 1
    assert C.routing_mismatches([SR(1, 0, b)], engines) == 1


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _cli(ROOT, "--workload", "yi6b-chat", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_refuses_in_a_bare_checkout(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, "--workload", "yi6b-chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
