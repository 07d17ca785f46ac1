"""Whether what the timed path produced is right.

After the window has closed and the program's state is freed, a sample
of the requests the window finished, drawn from the seed and always
holding the longest, is packed per model into rows of `pack_tokens`
tokens. The plain reference runs once over each prompt followed by its
served tokens. For every served token it reads how far that token's
reference logit lies below the reference's best at that position; the
number compared is the widest such gap. Greedy decoding serves the
program's own best token, so a sound program reads a gap of rounding
size, and a program that serves a wrong token reads the distance to the
right one.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from chipbench.traffic import rng


@dataclasses.dataclass
class Done:
    """A finished request: its prompt, served tokens and model."""

    prompt: List[int]
    tokens: List[int]
    model: str

    @property
    def length(self) -> int:
        return len(self.prompt) + len(self.tokens) - 1


def sample(done: Sequence[Done], seed: int, pack: int) -> List[Done]:
    """The longest request, then others in an order drawn from the seed,
    as many as fit into `pack` tokens."""
    fits = [d for d in done if 0 < d.length <= pack]
    if not fits:
        return []
    longest = max(range(len(fits)), key=lambda i: fits[i].length)
    order = [longest] + [i for i in rng(seed, 0xC4EC).permutation(
        len(fits)).tolist() if i != longest]
    out, used = [], 0
    for i in order:
        if used + fits[i].length <= pack:
            out.append(fits[i])
            used += fits[i].length
    return out


def pack_rows(reqs: Sequence[Done], pack: int):
    """Packed tokens, positions, segment ids, the rows whose logits
    predict each served token, and those tokens."""
    tokens = np.zeros(pack, np.int32)
    pos = np.zeros(pack, np.int32)
    seg = np.full(pack, -1, np.int32)
    rows, targets = [], []
    o = 0
    for s, r in enumerate(reqs):
        seq = r.prompt + r.tokens[:-1]
        n = len(seq)
        tokens[o:o + n] = seq
        pos[o:o + n] = np.arange(n)
        seg[o:o + n] = s
        p = len(r.prompt)
        rows.extend(range(o + p - 1, o + p - 1 + len(r.tokens)))
        targets.extend(r.tokens)
        o += n
    return tokens, pos, seg, np.array(rows, np.int32), np.array(targets)


def gaps(ref_logits, chosen) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best, per row."""
    ref = np.asarray(ref_logits, np.float64)
    chosen = np.asarray(chosen)
    return ref.max(axis=1) - ref[np.arange(len(chosen)), chosen]


@dataclasses.dataclass
class Verdict:
    gap: float                      # widest gap of the served tokens
    tokens: int                     # served tokens compared
    requests: int
    control_gap: Optional[float] = None


def compare(ref_module, m, key, reqs: Sequence[Done], pack: int,
            control: bool = False) -> Verdict:
    tokens, pos, seg, rows, targets = pack_rows(reqs, pack)
    ref, ctrl = ref_module.logits(m, key, jnp.asarray(tokens),
                                  jnp.asarray(pos), jnp.asarray(seg),
                                  jnp.asarray(rows), control=control)
    ref = np.asarray(ref)
    v = Verdict(gap=float(gaps(ref, targets).max()), tokens=len(targets),
                requests=len(reqs))
    if control:
        v.control_gap = float(gaps(ref, np.asarray(ctrl).argmax(1)).max())
    return v


def routing_mismatches(handles, engines) -> int:
    """Requests whose tokens did not come from the replica their decision
    named: the finished engine request must be on that engine."""
    bad = 0
    for sr in handles:
        if sr is None or sr.engine_req is None or not sr.engine_req.done:
            continue
        j = sr.decision.server if sr.decision is not None else -1
        if j < 0 or sr.server != j or not any(
                r is sr.engine_req for r in engines[j].completed):
            bad += 1
    return bad


def limits_report(checks: Dict[str, tuple]) -> Dict[str, Dict[str, float]]:
    """{name: {"value", "limit"}} from {name: (value, limit)}."""
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
