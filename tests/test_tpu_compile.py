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


def test_yi6b_admission_program_writes_cache_and_pools_in_place(one_chip):
    """The engine's one-program admission at Yi-6B's published widths, cut
    to 2 layers, for the benchmark's 8 lanes, 2,144-token lanes and
    1,072-page pool: every byte of the donated batch cache and page pools
    is aliased to the program's outputs (written in place, no copy of
    either), and its scratch is no more than the prefill program's own
    scratch and one-slot cache."""
    from repro.serving import ServingEngine
    cfg = dataclasses.replace(YI, n_layers=2)
    # no weights needed to compile: only the cache and pools are built
    eng = ServingEngine(cfg, None, max_batch=8, max_seq=2144, paged=True,
                        kv_blocks=1072)

    def placed(tree):
        return jax.tree.map(
            lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    cache = placed(jax.eval_shape(lambda: eng.cache))
    pools = placed(jax.eval_shape(lambda: eng.kv.pools))
    params = placed(M.params_shapes(cfg))
    i32 = jnp.int32
    bucket = 512
    batch = {"tokens": _sds((1, bucket), i32, one_chip)}
    last = _sds((), i32, one_chip)
    mem = eng._admit_prompt.lower(
        params, cache, pools, batch, last, _sds((), i32, one_chip),
        _sds((bucket // 16,), i32, one_chip),
        _sds((), jax.random.key(0).dtype, one_chip)).compile(
        ).memory_analysis()
    alone = jax.jit(eng._prefill_cache).lower(
        params, batch, last).compile().memory_analysis()
    assert mem.alias_size_in_bytes == nbytes(cache) + nbytes(pools)
    assert mem.temp_size_in_bytes <= alone.temp_size_in_bytes \
        + alone.output_size_in_bytes


# the fleet's cloud replica: one 8-layer stage of Yi-34B (56 query heads
# over 8 KV heads, d 7,168, d_ff 20,480) with the embedding and the head,
# 16 lanes of 2,144 tokens and a 2,144-page pool, on one v5e
YI34_STAGE = dataclasses.replace(get_config("yi-34b"), n_layers=8)
V5E_LIMIT_BYTES = 16.91e9      # what a v5e reports as its bytes_limit
CLOUD_LANES, CLOUD_SEQ, CLOUD_PAGES = 16, 2144, 2144


def _stage_engine_shapes(one_chip):
    """The stage's engine programs with the shapes of the cloud replica.
    The engine is built at one lane and one page (its programs depend only
    on the shapes they are given), so no full-size cache is held here."""
    from repro.serving import ServingEngine
    eng = ServingEngine(YI34_STAGE, None, max_batch=1, max_seq=CLOUD_SEQ,
                        paged=True, kv_blocks=1)

    def placed(tree):
        return jax.tree.map(
            lambda s: _sds(s.shape, s.dtype, one_chip), tree)
    params = placed(M.params_shapes(YI34_STAGE))
    cache = placed(M.cache_specs(YI34_STAGE, CLOUD_LANES, CLOUD_SEQ))
    pools = [_sds((CLOUD_PAGES,) + p.shape[1:], p.dtype, one_chip)
             for p in eng.kv.pools]
    return eng, params, cache, pools


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_yi34b_stage_decode_step_fits_one_v5e(one_chip):
    """The cloud replica's decode program at every published width of
    Yi-34B: weights (10.76e9 bytes), cache, its undonated cache output and
    scratch, with the page pool resident beside them, fit one chip."""
    eng, params, cache, pools = _stage_engine_shapes(one_chip)
    mem = eng._decode.lower(
        params, _sds((CLOUD_LANES, 1), jnp.int32, one_chip), cache,
        _sds((CLOUD_LANES,), jnp.int32, one_chip)).compile(
        ).memory_analysis()
    assert mem.argument_size_in_bytes > 10.7e9
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            + _nbytes(pools))
    assert need < V5E_LIMIT_BYTES, need


def test_yi34b_stage_admission_program_fits_one_v5e(one_chip):
    """The cloud replica's one-program admission at the longest prompt's
    bucket (2,048 tokens): batch cache and page pools donated and written
    in place, and the program fits one chip."""
    eng, params, cache, pools = _stage_engine_shapes(one_chip)
    i32, bucket = jnp.int32, 2048
    mem = eng._admit_prompt.lower(
        params, cache, pools, {"tokens": _sds((1, bucket), i32, one_chip)},
        _sds((), i32, one_chip), _sds((), i32, one_chip),
        _sds((bucket // 16,), i32, one_chip),
        _sds((), jax.random.key(0).dtype, one_chip)).compile(
        ).memory_analysis()
    assert mem.alias_size_in_bytes == _nbytes(cache) + _nbytes(pools)
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert need < V5E_LIMIT_BYTES, need
