"""Operations and bytes of one model step, from the configuration's
shapes alone (never from the program).

Counts are of what the algorithm needs: a decode step streams every
weight once and reads the keys and values of the positions each active
lane has in use, not the whole reserved cache.
"""
from __future__ import annotations

from typing import Sequence

from chipbench.model import Shape


def layer_matmul_params(m: Shape) -> int:
    q = m.d_model * m.n_heads * m.head_dim
    kv = 2 * m.d_model * m.n_kv_heads * m.head_dim
    o = m.n_heads * m.head_dim * m.d_model
    mlp = 3 * m.d_model * m.d_ff
    return q + kv + o + mlp


def matmul_params(m: Shape) -> int:
    """Parameters multiplied per token: every layer's projections and the
    output head (the embedding is a gather)."""
    return m.n_layers * layer_matmul_params(m) + m.vocab * m.d_model


def kv_bytes_per_token(m: Shape) -> int:
    return m.n_layers * 2 * m.n_kv_heads * m.head_dim * m.bytes_per_param


def weight_bytes(m: Shape) -> int:
    """Bytes of the weights a decode step streams: projections, output
    head and the norm scales (float32)."""
    norms = (2 * m.n_layers + 1) * m.d_model * 4
    return matmul_params(m) * m.bytes_per_param + norms


def attention_flops(m: Shape, ctx: int) -> int:
    """Scores and weighted sum of one query token over `ctx` keys."""
    return 4 * m.n_layers * m.n_heads * m.head_dim * ctx


def decode_step_flops(m: Shape, ctx_lens: Sequence[int]) -> int:
    """One decode step over lanes whose queries see `ctx_lens` keys."""
    return sum(2 * matmul_params(m) + attention_flops(m, c)
               for c in ctx_lens)


def decode_step_bytes(m: Shape, ctx_lens: Sequence[int]) -> int:
    """Weights once, each lane's keys and values in use, the new token's
    keys and values written, and the embedding rows gathered."""
    kv = kv_bytes_per_token(m)
    lanes = len(ctx_lens)
    return (weight_bytes(m) + kv * sum(ctx_lens) + kv * lanes
            + lanes * m.d_model * m.bytes_per_param)


def decode_step_least_s(m: Shape, ctx_lens: Sequence[int],
                        peak_flops: float, peak_bw: float) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(decode_step_flops(m, ctx_lens) / peak_flops,
               decode_step_bytes(m, ctx_lens) / peak_bw)
