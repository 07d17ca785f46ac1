"""jit'd public wrappers around the Pallas kernels.

The wrappers take model-layout tensors (B, S, H, D) and transpose them for
the kernels. On a TPU they lower to Mosaic. Interpret mode, where the
kernel body runs in Python, exists only for the CPU tests: `interpret=None`
(auto) picks it when the backend is the CPU, because Pallas-TPU cannot
lower there.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _paged
from repro.kernels import ref as _ref


@functools.lru_cache(maxsize=None)
def _backend_is_cpu() -> bool:
    # the backend cannot change within a process; probing it resolves the
    # whole JAX platform stack, so pay that once, not per kernel call
    return jax.default_backend() == "cpu"


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return _backend_is_cpu()
    if not interpret and _backend_is_cpu():
        raise RuntimeError(
            "Pallas-TPU lowering is unavailable on the CPU backend but "
            "interpret=False was forced; pass interpret=None (auto) or "
            "interpret=True to run the kernel in interpret mode")
    return bool(interpret)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, scale: float = 1.0,
                    interpret: Optional[bool] = None):
    """Model-layout flash attention. q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D).

    The kernel computes its mask from block iota and (causal, window,
    q_offset); it takes no explicit (Sq, Sk) mask, so a caller that holds
    one must use the jnp path.
    """
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _fa.flash_attention(
        qt, kt, vt, causal=causal, window=window, q_offset=q_offset,
        scale=scale, interpret=_auto_interpret(interpret))
    return jnp.swapaxes(out, 1, 2)


def decode_attention(q, k, v, valid_len, *, scale: float = 1.0,
                     interpret: Optional[bool] = None):
    """q: (B, 1, Hq, D) or (B, Hq, D); k/v: (B, S, Hkv, D)."""
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _dec.decode_attention(q, kt, vt, valid_len, scale=scale,
                                interpret=_auto_interpret(interpret))
    return out[:, None] if squeeze else out


def paged_attention(q, k_pages, v_pages, block_tables, valid_len, *,
                    scale: float = 1.0, interpret: Optional[bool] = None):
    """Decode attention over a paged KV pool.

    q: (B, 1, Hq, D) or (B, Hq, D); k_pages/v_pages in model layout
    (n_pool, page_size, Hkv, D); block_tables: (B, n_pages) page ids
    (pad with 0); valid_len: scalar or (B,) valid tokens per request.
    """
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    kt = jnp.swapaxes(k_pages, 1, 2)
    vt = jnp.swapaxes(v_pages, 1, 2)
    out = _paged.paged_attention(q, kt, vt, block_tables, valid_len,
                                 scale=scale,
                                 interpret=_auto_interpret(interpret))
    return out[:, None] if squeeze else out


# re-export oracles for tests/benchmarks
flash_attention_ref = _ref.flash_attention_ref
decode_attention_ref = _ref.decode_attention_ref
paged_attention_ref = _ref.paged_attention_ref
