"""Plain float32 reference of a Llama-style decoder, in bounded memory.

The same model and arithmetic as `references/llama.py` (RMSNorm with
weights 1, grouped-query attention with rotary embeddings on the two
halves of each head, causal, SwiGLU, final RMSNorm, untied head, every
matmul at `Precision.HIGHEST`, and the same float8 `control` stream),
made to run beside a fleet that still holds its chips' memory:

- it runs on the device with the most free memory;
- each weight matrix is made from the seed on its own (the benchmark's
  generator, the other matrices of its layer left out), and widened to
  float32 a block of columns at a time inside the matmul;
- attention runs over blocks of `Q_BLOCK` queries, the MLP over blocks
  of `T_BLOCK` tokens, and the embedding's rows are gathered before they
  are widened.

Blocks split only axes a matmul does not sum over, and each float8 scale
is taken over the summed axis, so no sum and no scale changes. For Yi-34B
over 6,144 packed tokens, as compiled for a v5e, a layer's call holds at
most 1.6 GB and the head's about 2.1 GB; `llama.py` holds 2.4 GB a layer
and 2.8 GB to widen the embedding, on the default device, where a Yi-6B
edge replica leaves 2.6 GB free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.model import Shape
from chipbench.spec import reference

# the plain reference's pieces: float8 rounding, einsum, norm, rotary
_PLAIN = reference("llama")
HI, fp8, _ein, _rms, _rope = (_PLAIN.HI, _PLAIN.fp8, _PLAIN._ein,
                              _PLAIN._rms, _PLAIN._rope)
Q_BLOCK = 128
T_BLOCK = 1024
COLS = 2048          # widest block of a weight's columns widened at once


def _blocks(n: int, most: int) -> int:
    """The fewest equal blocks of `n` no wider than `most`."""
    return next(b for b in range(1, n + 1) if n % b == 0 and n // b <= most)


def _mm(a, w, low=False, rows=False):
    """`a @ w` in float32 (`a` (T, k) float32, `w` (k, n) in its own
    dtype), widening `w` one block of columns at a time; with `rows`,
    `a @ w.T` for `w` (n, k), a block of rows at a time."""
    n = w.shape[0] if rows else w.shape[1]
    bs = n // _blocks(n, COLS)
    if low:
        a = fp8(a, -1)

    def block(i):
        wb = jax.lax.dynamic_slice_in_dim(w, i * bs, bs, 0 if rows else 1)
        wb = wb.astype(jnp.float32)
        if rows:
            wb = wb.T
        if low:
            wb = fp8(wb, 0)
        return jnp.matmul(a, wb, precision=HI)

    out = jax.lax.map(block, jnp.arange(n // bs))
    return jnp.moveaxis(out, 0, 1).reshape(a.shape[0], n)


@functools.partial(jax.jit, static_argnames=("name", "m"))
def _weight(key, layer, name: str, m: Shape):
    """One matrix of layer `layer`, as `weights.gen_layer` makes it (one
    program a matrix, whatever the layer)."""
    return W.gen_layer(key, layer, m)[name]


@functools.partial(jax.jit, static_argnames=("m", "low"))
def _attention(h, wq, wk, wv, wo, pos, seg, m: Shape, low: bool = False):
    t = h.shape[0]
    x = _rms(h, m.norm_eps)
    q = _mm(x, wq, low).reshape(t, m.n_kv_heads, m.n_heads // m.n_kv_heads,
                                m.head_dim)
    k = _mm(x, wk, low).reshape(t, m.n_kv_heads, m.head_dim)
    v = _mm(x, wv, low).reshape(t, m.n_kv_heads, m.head_dim)
    q = _rope(q.reshape(t, m.n_heads, m.head_dim), pos,
              m.rope_theta).reshape(q.shape)
    k = _rope(k, pos, m.rope_theta)
    scale = m.head_dim ** -0.5

    def block(i):
        lo = i * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(q, lo, Q_BLOCK, 0)
        pb = jax.lax.dynamic_slice_in_dim(pos, lo, Q_BLOCK, 0)
        sb = jax.lax.dynamic_slice_in_dim(seg, lo, Q_BLOCK, 0)
        s = _ein("qhgd,khd->hgqk", qb, k, -1, -1, low) * scale
        ok = (sb[:, None] == seg[None, :]) & (pos[None, :] <= pb[:, None])
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("hgqk,khd->qhgd", p, v, -1, 0, low)

    att = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    return h + _mm(att.reshape(t, m.n_heads * m.head_dim), wo, low)


@functools.partial(jax.jit, static_argnames=("m", "low"))
def _mlp(h, w_gate, w_up, w_down, m: Shape, low: bool = False):
    t, d = h.shape
    nb = _blocks(t, T_BLOCK)

    def block(hb):
        x = _rms(hb, m.norm_eps)
        g = _mm(x, w_gate, low)
        return hb + _mm(jax.nn.silu(g) * _mm(x, w_up, low), w_down, low)

    return jax.lax.map(block, h.reshape(nb, t // nb, d)).reshape(t, d)


@functools.partial(jax.jit, static_argnames=("m", "low"))
def _head(h, unembed, m: Shape, low: bool = False):
    return _mm(_rms(h, m.norm_eps), unembed, low, rows=True)


@jax.jit
def _embed_rows(embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


def _freest_device():
    def free(d):
        st = d.memory_stats() or {}
        return st.get("bytes_limit", 0) - st.get("bytes_in_use", 0)
    return max(jax.local_devices(), key=free)


def logits(m: Shape, key, tokens, positions, segments, rows,
           control: bool = False):
    """Reference logits (float32) at the packed `rows`, and with
    `control` the float8 stream's logits there too."""
    if tokens.shape[0] % Q_BLOCK:
        raise ValueError(f"packed rows of {tokens.shape[0]} tokens are not "
                         f"a whole number of {Q_BLOCK}-query blocks")
    dev = _freest_device()
    key, tokens, positions, segments, rows = jax.device_put(
        (key, tokens, positions, segments, rows), dev)
    embed = W.gen_embed_jit(key, m)
    h = _embed_rows(embed, tokens)
    # float8 rows of the embedding, each with its own scale
    hc = fp8(h, 1) if control else None
    del embed
    for i in range(m.n_layers):
        att = [_weight(key, i, n, m) for n in ("wq", "wk", "wv", "wo")]
        h = _attention(h, *att, positions, segments, m)
        if control:
            hc = _attention(hc, *att, positions, segments, m, low=True)
        del att
        mlp = [_weight(key, i, n, m) for n in ("w_gate", "w_up", "w_down")]
        h = _mlp(h, *mlp, m)
        if control:
            hc = _mlp(hc, *mlp, m, low=True)
        del mlp
    unembed = W.gen_unembed_jit(key, m)
    out = _head(h[rows], unembed, m)
    ctrl = _head(hc[rows], unembed, m, low=True) if control else None
    return out, ctrl
