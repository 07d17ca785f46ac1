"""Random weights from the seed, made by the benchmark.

The served program and the plain reference both take their weights from
here, so the reference takes nothing that the program made. A layer's
weights depend only on (seed, model, layer index): the reference
regenerates one layer at a time, and the served stack is the same
generator mapped over every layer in one jitted call on the device.
Scales follow the usual fan-in initialisation; norm weights are 1.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.model import Shape

LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def model_key(seed: int, model: str):
    """A threefry key from a seed of any size and the model's name."""
    words = np.random.SeedSequence(
        [int(seed) % (1 << 64), zlib.crc32(model.encode())]
    ).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def layer_shapes(m: Shape):
    d, q, kv, f = (m.d_model, m.n_heads * m.head_dim,
                   m.n_kv_heads * m.head_dim, m.d_ff)
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def gen_layer(key, layer, m: Shape):
    """Layer `layer`'s projection weights, in the served dtype."""
    ks = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, 1), layer), len(LAYER_KEYS))
    shapes = layer_shapes(m)
    return {name: _normal(k, shapes[name], 1.0 / np.sqrt(shapes[name][0]),
                          jnp.dtype(m.dtype))
            for name, k in zip(LAYER_KEYS, ks, strict=True)}


def gen_embed(key, m: Shape):
    return _normal(jax.random.fold_in(key, 2), (m.vocab, m.d_model), 0.02,
                   jnp.dtype(m.dtype))


def gen_unembed(key, m: Shape):
    return _normal(jax.random.fold_in(key, 3), (m.vocab, m.d_model),
                   1.0 / np.sqrt(m.d_model), jnp.dtype(m.dtype))


@functools.partial(jax.jit, static_argnames="m")
def gen_all(key, m: Shape):
    """Every weight, the layers stacked on a leading axis."""
    layers = jax.vmap(lambda i: gen_layer(key, i, m))(
        jnp.arange(m.n_layers))
    return {"embed": gen_embed(key, m), "unembed": gen_unembed(key, m),
            "layers": layers}


gen_layer_jit = jax.jit(gen_layer, static_argnames="m")
gen_embed_jit = jax.jit(gen_embed, static_argnames="m")
gen_unembed_jit = jax.jit(gen_unembed, static_argnames="m")
