"""The one traffic generator: a mix file's parameters + a seed -> requests.

A mix file (`traffic/<name>.json`) gives a `kind` and its parameters.

`open_poisson`: independent users arriving at `rate_rps`. Each marginal
(`prompt_tokens`, `output_tokens`, `deadline_s`, `payload_bytes`) is a
distribution spec. So that every seed offers the same work, the arrival
times and the sizes of a window of `seconds` are drawn once from the
mix's `population_seed`: the arrivals of a Poisson process conditioned on
`round(rate * seconds)` of them in the window. The run's seed draws the
token ids.

`payload_bytes` may instead be `{"dist": "per_prompt_token",
"bytes_per_token": b}`: each request carries its prompt's text, `b`
bytes a token.

Token ids are uniform over the vocabulary, so no two prompts share a
prefix of even one 16-token block.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np


def rng(*words: int) -> np.random.Generator:
    """A generator keyed by whole numbers of any size (seeds past 2**32)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(w) % (1 << 64) for w in words]))


def draw(spec: Dict[str, Any], n: int, g: np.random.Generator) -> np.ndarray:
    """`n` values of the distribution `spec`."""
    kind = spec["dist"]
    if kind == "lognormal":
        x = g.lognormal(spec["mu"], spec["sigma"], n)
    elif kind == "uniform":
        x = g.uniform(spec["low"], spec["high"], n)
    elif kind == "const":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec or "max" in spec:
        x = np.clip(x, spec.get("min", -np.inf), spec.get("max", np.inf))
    if spec.get("integer", kind == "lognormal"):
        x = x.astype(np.int64)      # truncation, as the paper's generator
    return x


@dataclasses.dataclass
class Requests:
    """Timed requests, ordered by due time (seconds from the window's
    start)."""

    due: np.ndarray
    prompts: List[List[int]]
    max_new: np.ndarray
    deadline: np.ndarray
    payload: np.ndarray

    def __len__(self) -> int:
        return len(self.due)

    @property
    def prompt_lens(self) -> np.ndarray:
        return np.array([len(p) for p in self.prompts], np.int64)


def n_requests(mix: Dict[str, Any], seconds: float) -> int:
    return max(1, int(round(mix["rate_rps"] * seconds)))


def population(mix: Dict[str, Any], seconds: float):
    """The fixed multiset of a window: gaps and per-request sizes."""
    n = n_requests(mix, seconds)
    g = rng(mix["population_seed"], n)
    gaps = np.diff(np.concatenate(
        [[0.0], np.sort(g.uniform(0.0, seconds, n))]))
    sizes = {k: draw(mix[k], n, g) for k in
             ("prompt_tokens", "output_tokens", "deadline_s")}
    pay = mix["payload_bytes"]
    if pay["dist"] == "per_prompt_token":
        sizes["payload_bytes"] = (sizes["prompt_tokens"]
                                  * float(pay["bytes_per_token"]))
    else:
        sizes["payload_bytes"] = draw(pay, n, g)
    return gaps, sizes


def open_poisson(mix: Dict[str, Any], vocab: int, seed: int,
                 seconds: float, stream: int = 0) -> Requests:
    """The window's requests for `seed`. `stream` picks an independent
    draw of token ids (the warm-up uses its own)."""
    if mix["kind"] != "open_poisson":
        raise ValueError(f"not an open-loop mix: {mix['kind']!r}")
    gaps, sizes = population(mix, seconds)
    g = rng(seed, stream, 0x7AFF1C)
    prompts = [g.integers(0, vocab, int(p)).tolist()
               for p in sizes["prompt_tokens"]]
    return Requests(due=np.cumsum(gaps), prompts=prompts,
                    max_new=sizes["output_tokens"].astype(np.int64),
                    deadline=sizes["deadline_s"].astype(float),
                    payload=sizes["payload_bytes"].astype(float))


GENERATORS = {"open_poisson": open_poisson}


def generate(mix: Dict[str, Any], vocab: int, seed: int, seconds: float,
             stream: int = 0) -> Requests:
    if mix["kind"] not in GENERATORS:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return GENERATORS[mix["kind"]](mix, vocab, seed, seconds, stream)
