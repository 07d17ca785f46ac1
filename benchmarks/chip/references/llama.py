"""Plain float32 reference of a Llama-style decoder (Yi's architecture).

Pre-norm residual blocks: RMSNorm (weights 1), grouped-query attention
with rotary embeddings on the two halves of each head (theta from the
configuration), causal; SwiGLU MLP; final RMSNorm; an untied output
head. Every matmul at `Precision.HIGHEST`. It imports nothing of the
served program: it regenerates each layer's weights from the seed with
the benchmark's generator, one layer at a time, so only one layer is on
the device in float32 at once.

Several sequences are packed into one row of `pack` tokens; `segments`
keeps them from attending to each other and `positions` restarts at 0 for
each, so each sees exactly what it would alone.

`control` runs a second stream beside the reference in the precision
below the served one (bfloat16): float8 (e4m3) matmuls. Both operands of
every matmul, attention's included, are rounded to float8 with a scale
per row or column (over the reduced axis), and products are summed in
float32, as a float8 matrix unit does; norms, rotary embeddings and the
softmax stay in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.model import Shape

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
F8_MAX = 448.0


def fp8(w, axis):
    """`w` rounded to float8 e4m3, with one scale per slice taken over
    `axis` (the axis a matmul sums over)."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, low=False):
    if low:
        a, b = fp8(a, -1), fp8(b, 0)
    return jnp.matmul(a, b, precision=HI)


def _ein(spec, a, b, a_axis, b_axis, low):
    if low:
        a, b = fp8(a, a_axis), fp8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("m", "low"))
def layer(h, w, pos, seg, m: Shape, low: bool = False):
    """One decoder block over a packed row `h` (T, d), float32; with
    `low`, its matmuls in float8."""
    t = h.shape[0]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = _rms(h, m.norm_eps)
    q = _mm(x, w["wq"], low).reshape(t, m.n_kv_heads,
                                     m.n_heads // m.n_kv_heads, m.head_dim)
    k = _mm(x, w["wk"], low).reshape(t, m.n_kv_heads, m.head_dim)
    v = _mm(x, w["wv"], low).reshape(t, m.n_kv_heads, m.head_dim)
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
    att = att.reshape(t, m.n_heads * m.head_dim)
    h = h + _mm(att, w["wo"], low)
    x = _rms(h, m.norm_eps)
    g = _mm(x, w["w_gate"], low)
    return h + _mm(jax.nn.silu(g) * _mm(x, w["w_up"], low), w["w_down"],
                   low)


@functools.partial(jax.jit, static_argnames=("m", "low"))
def head(h, unembed, m: Shape, low: bool = False):
    return _mm(_rms(h, m.norm_eps), unembed.astype(jnp.float32).T, low)


_fp8_rows = jax.jit(lambda w: fp8(w, 1))


def logits(m: Shape, key, tokens, positions, segments, rows,
           control: bool = False):
    """Reference logits (float32) at the packed `rows`, and with
    `control` the float8 stream's logits there too."""
    embed = W.gen_embed_jit(key, m)
    h = jnp.take(embed.astype(jnp.float32), tokens, axis=0)
    hc = jnp.take(_fp8_rows(embed), tokens, axis=0) if control else None
    del embed
    for i in range(m.n_layers):
        w = W.gen_layer_jit(key, i, m)
        h = layer(h, w, positions, segments, m)
        if control:
            hc = layer(hc, w, positions, segments, m, low=True)
        del w
    unembed = W.gen_unembed_jit(key, m)
    out = head(h[rows], unembed, m)
    ctrl = head(hc[rows], unembed, m, low=True) if control else None
    return out, ctrl
