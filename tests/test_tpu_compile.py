"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX, so these tests lower the serving
path's Pallas kernels to Mosaic at Yi-6B's head layout (32 query heads,
4 KV heads, head_dim 128, bf16) and compile Yi-6B's decode step at its
published widths. Nothing runs: they catch what the chip's compiler
refuses (tiling, fast-memory limits, a program that does not fit) before
a chip run does.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.models import cpu_context
from repro.models import model as M

YI = get_config("yi-6b")
HQ, HKV, D = YI.n_heads, YI.n_kv_heads, YI.resolved_head_dim
BF16 = jnp.bfloat16
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one; keep these out of any such cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seq", [8, 512, 2048])
def test_flash_attention_compiles_for_v5e(one_chip, seq):
    q = _sds((1, HQ, seq, D), BF16, one_chip)
    kv = _sds((1, HKV, seq, D), BF16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=D ** -0.5)).lower(q, kv, kv).compile()
    _assert_mosaic(compiled)


def test_decode_attention_compiles_for_v5e(one_chip):
    q = _sds((8, HQ, D), BF16, one_chip)
    kv = _sds((8, HKV, 2048, D), BF16, one_chip)
    vl = _sds((), jnp.int32, one_chip)
    compiled = jax.jit(lambda q, k, v, vl: decode_attention(
        q, k, v, vl, scale=D ** -0.5)).lower(q, kv, kv, vl).compile()
    _assert_mosaic(compiled)


def test_paged_attention_compiles_for_v5e(one_chip):
    batch, page, n_pages = 8, 16, 128
    q = _sds((batch, HQ, D), BF16, one_chip)
    pages = _sds((batch * n_pages + 1, HKV, page, D), BF16, one_chip)
    tables = _sds((batch, n_pages), jnp.int32, one_chip)
    vl = _sds((batch,), jnp.int32, one_chip)
    compiled = jax.jit(lambda q, k, v, t, vl: paged_attention(
        q, k, v, t, vl, scale=D ** -0.5)).lower(
            q, pages, pages, tables, vl).compile()
    _assert_mosaic(compiled)


def test_yi6b_decode_step_fits_one_v5e(one_chip):
    """The engine's decode program (per-slot positions) at Yi-6B's
    published widths, cut to 2 layers, for 8 lanes of 2048 tokens."""
    cfg = dataclasses.replace(YI, n_layers=2)
    batch, max_seq = 8, 2048

    def placed(tree):
        return jax.tree.map(
            lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = placed(M.params_shapes(cfg))
    cache = placed(M.cache_specs(cfg, batch, max_seq))
    tokens = _sds((batch, 1), jnp.int32, one_chip)
    pos = _sds((batch,), jnp.int32, one_chip)
    compiled = jax.jit(lambda p, t, c, pos: M.decode_step(
        p, t, c, pos, cfg=cfg, ctx=cpu_context())).lower(
            params, tokens, cache, pos).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # 2 layers of weights plus both 64000-token embedding tables
    assert mem.argument_size_in_bytes > 2 * 64000 * 4096 * 2
    assert need < V5E_HBM_BYTES
