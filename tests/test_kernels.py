"""Pallas kernel correctness: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run in interpret mode on CPU (the kernel body executes in Python);
the same pallas_call lowers to Mosaic on a real TPU backend.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import decode_attention_ref, flash_attention_ref
from repro.models.layers import flash_jnp_call, sdpa

KEY = jax.random.key(42)


def _qkv(b, sq, sk, hq, hkv, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("shape", [
    (1, 128, 128, 2, 2, 64),     # MHA
    (2, 256, 256, 4, 2, 64),     # GQA
    (1, 256, 256, 8, 1, 128),    # MQA, head_dim 128
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
def test_flash_attention_kernel(shape, dtype, tol, causal, window):
    b, sq, sk, hq, hkv, d = shape
    q, k, v = _qkv(b, sq, sk, hq, hkv, d, dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          scale=1.0 / np.sqrt(d), block_q=128, block_k=128,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              scale=1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("hq,hkv,s", [(4, 4, 512), (8, 2, 1024), (8, 1, 512)])
@pytest.mark.parametrize("valid", [1, 7, 350, -1])
def test_decode_attention_kernel(dtype, tol, hq, hkv, s, valid):
    b, d = 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32).astype(dtype)
    vl = s if valid == -1 else valid
    out = decode_attention(q, k, v, vl, scale=0.125, block_k=256,
                           interpret=True)
    ref = decode_attention_ref(q, k, v, vl, scale=0.125)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("hq,hkv,page", [(4, 4, 16), (8, 2, 32), (8, 1, 64)])
@pytest.mark.parametrize("valid", [1, 7, 50, -1])
def test_paged_attention_matches_contiguous_decode(dtype, tol, hq, hkv,
                                                   page, valid):
    """Scattering a contiguous cache into shuffled pool pages and reading
    it back through the page table must reproduce `decode_attention`
    exactly (the paged kernel is the same math behind an indirection)."""
    from repro.kernels.paged_attention import paged_attention

    b, d, n_pages = 2, 64, 128 // page
    s = n_pages * page
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32).astype(dtype)
    vl = s if valid == -1 else valid

    # scatter each request's logical pages to shuffled physical pool slots
    n_pool = b * n_pages + 3
    perm = jax.random.permutation(ks[3], n_pool)[: b * n_pages]
    tables = perm.reshape(b, n_pages).astype(jnp.int32)
    k_pages = jnp.zeros((n_pool, hkv, page, d), dtype)
    v_pages = jnp.zeros((n_pool, hkv, page, d), dtype)
    # (B, Hkv, n_pages, page, D) -> (B, n_pages, Hkv, page, D)
    k_split = jnp.swapaxes(k.reshape(b, hkv, n_pages, page, d), 1, 2)
    v_split = jnp.swapaxes(v.reshape(b, hkv, n_pages, page, d), 1, 2)
    k_pages = k_pages.at[tables.reshape(-1)].set(
        k_split.reshape(-1, hkv, page, d))
    v_pages = v_pages.at[tables.reshape(-1)].set(
        v_split.reshape(-1, hkv, page, d))

    out = paged_attention(q, k_pages, v_pages, tables, vl, scale=0.125,
                          interpret=True)
    contiguous = decode_attention(q, k, v, vl, scale=0.125,
                                  block_k=min(page, 256), interpret=True)
    ref = decode_attention_ref(q, k, v, vl, scale=0.125)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(contiguous, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_attention_ragged_valid_lengths():
    """Per-request valid lengths (a real continuous batch is ragged) vs the
    page-gathering oracle; padded table entries may alias live pages of
    other requests and must stay masked."""
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    b, hq, hkv, d, page, n_pages = 3, 8, 2, 64, 16, 8
    n_pool = b * n_pages
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, hq, d))
    k_pages = jax.random.normal(ks[1], (n_pool, hkv, page, d))
    v_pages = jax.random.normal(ks[2], (n_pool, hkv, page, d))
    tables = jax.random.permutation(
        ks[3], n_pool).reshape(b, n_pages).astype(jnp.int32)
    # valid_len 0 is the degenerate fully-masked row: both kernel and
    # oracle reduce to the uniform softmax over masked scores — pinned
    # here so the agreement (not the absolute value) is the contract
    vl = jnp.array([0, 57, page * n_pages], jnp.int32)
    out = paged_attention(q, k_pages, v_pages, tables, vl, scale=0.125,
                          interpret=True)
    ref = paged_attention_ref(q, k_pages, v_pages, tables, vl, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # model-layout ops wrapper agrees (auto-interpret on CPU)
    from repro.kernels import ops
    out2 = ops.paged_attention(q[:, None], jnp.swapaxes(k_pages, 1, 2),
                               jnp.swapaxes(v_pages, 1, 2), tables, vl,
                               scale=0.125)
    np.testing.assert_allclose(np.asarray(out2[:, 0]), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_jnp_matches_sdpa():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 1024, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 1024, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 1024, 2, 32), jnp.float32)
    qi = jnp.arange(1024)[:, None]
    kj = jnp.arange(1024)[None, :]
    for window in (0, 256):
        mask = (kj <= qi)
        if window:
            mask = mask & (kj > qi - window)
        o1 = flash_jnp_call(q, k, v, causal=True, window=window, scale=0.2,
                            block_q=256, block_k=256)
        o2 = sdpa(q, k, v, mask[None, None, None], 0.2)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-4, atol=2e-4)


def test_flash_jnp_custom_vjp_matches_autodiff():
    """FA2 manual backward == autodiff through the reference sdpa."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 512, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 512, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 512, 2, 32), jnp.float32)
    qi = jnp.arange(512)[:, None]
    kj = jnp.arange(512)[None, :]
    mask = (kj <= qi) & (kj > qi - 128)

    def f1(q, k, v):
        return jnp.sum(jnp.sin(flash_jnp_call(
            q, k, v, causal=True, window=128, scale=0.2,
            block_q=128, block_k=128)))

    def f2(q, k, v):
        return jnp.sum(jnp.sin(sdpa(q, k, v, mask[None, None, None], 0.2)))

    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_ssd_chunked_matches_recurrence():
    """Chunk-parallel SSD == naive per-token recurrence."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 2, 64, 4, 8, 16
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(KEY, 9), (b, s, n)) * 0.3

    y, final = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)

    # naive recurrence oracle
    hstate = np.zeros((b, h, p, n))
    ys = np.zeros((b, s, h, p))
    xn, dtn, Bn, Cn = map(np.asarray, (x, dt, Bm, Cm))
    An = np.asarray(A)
    for t in range(s):
        da = np.exp(dtn[:, t] * An)                       # (b, h)
        upd = np.einsum("bh,bhp,bn->bhpn", dtn[:, t], xn[:, t], Bn[:, t])
        hstate = hstate * da[:, :, None, None] + upd
        ys[:, t] = np.einsum("bn,bhpn->bhp", Cn[:, t], hstate)
    np.testing.assert_allclose(np.asarray(y), ys, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), hstate, rtol=2e-4,
                               atol=2e-4)


def test_rglru_scan_matches_recurrence():
    from repro.models.rglru import _gates
    dr = 16
    p = {"w_a": jnp.zeros(dr), "b_a": jnp.zeros(dr),
         "w_x": jnp.zeros(dr), "b_x": jnp.zeros(dr),
         "lam": jnp.ones(dr) * 0.5}
    u = jax.random.normal(KEY, (2, 32, dr))
    a, gi = _gates(p, u)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    a_s, h = jax.lax.associative_scan(combine, (a, gi), axis=1)
    # sequential oracle
    hn = np.zeros((2, dr))
    an, gn = np.asarray(a), np.asarray(gi)
    for t in range(32):
        hn = an[:, t] * hn + gn[:, t]
        np.testing.assert_allclose(np.asarray(h[:, t]), hn, rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("shape", [
    (2, 64, 4, 8, 16, 16), (1, 256, 2, 64, 128, 128), (2, 128, 8, 32, 64, 64),
])
def test_ssd_diag_kernel(shape):
    from repro.kernels.ref import ssd_diag_ref
    from repro.kernels.ssd_diag import ssd_diag
    b, s, h, d, n, chunk = shape
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.3
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.3
    out = ssd_diag(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_diag_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_model_forward_with_pallas_path():
    """use_pallas=True (interpret on CPU) == pure jnp forward."""
    from repro.configs import get_config
    from repro.models import cpu_context, dummy_batch, forward, init_params
    for arch in ("mamba2-2.7b", "gemma-2b"):
        cfg = get_config(arch).reduced()
        params = init_params(jax.random.key(0), cfg)
        batch = dummy_batch(jax.random.key(1), cfg, 1, 32, "train")
        ctx0 = cpu_context(remat=False)
        ctx1 = cpu_context(remat=False, use_pallas=True)
        l0, _, _ = forward(params, batch, cfg=cfg, ctx=ctx0, mode="train")
        l1, _, _ = forward(params, batch, cfg=cfg, ctx=ctx1, mode="train")
        np.testing.assert_allclose(np.asarray(l0, np.float32),
                                   np.asarray(l1, np.float32),
                                   rtol=3e-2, atol=3e-2)


def test_decode_with_pallas_matches_jnp():
    """decode_step with ctx.use_pallas == plain jnp decode (gemma-2b MQA)."""
    from repro.configs import get_config
    from repro.models import (
        cpu_context, decode_step, init_cache, init_params, prefill,
    )
    cfg = get_config("gemma-2b").reduced()
    params = init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 20), 0, cfg.vocab_size)
    outs = []
    for use_pallas in (False, True):
        ctx = cpu_context(remat=False, use_pallas=use_pallas)
        cache = init_cache(cfg, 2, 64)
        _, cache = prefill(params, {"tokens": toks[:, :16]}, cache,
                           cfg=cfg, ctx=ctx)
        l, _ = decode_step(params, toks[:, 16:17], cache, jnp.int32(16),
                           cfg=cfg, ctx=ctx)
        outs.append(np.asarray(l, np.float32))
    np.testing.assert_allclose(outs[0], outs[1], rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# dtype/shape parity sweep: non-power-of-two head dims (Qwen-style d=80,
# narrow d=48) through decode + paged decode vs the jnp oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("d", [48, 80])
def test_decode_attention_parity_nonpow2_head_dim(dtype, tol, d):
    b, hq, hkv, s = 2, 8, 2, 256
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32).astype(dtype)
    for vl in (1, 100, s):
        out = decode_attention(q, k, v, vl, scale=d ** -0.5, block_k=128,
                               interpret=True)
        ref = decode_attention_ref(q, k, v, vl, scale=d ** -0.5)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("d", [48, 80])
def test_paged_attention_parity_nonpow2_head_dim(dtype, tol, d):
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    b, hq, hkv, page, n_pages = 2, 8, 2, 16, 8
    n_pool = b * n_pages
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    k_pages = jax.random.normal(
        ks[1], (n_pool, hkv, page, d), jnp.float32).astype(dtype)
    v_pages = jax.random.normal(
        ks[2], (n_pool, hkv, page, d), jnp.float32).astype(dtype)
    tables = jax.random.permutation(
        ks[3], n_pool).reshape(b, n_pages).astype(jnp.int32)
    vl = jnp.array([37, page * n_pages], jnp.int32)
    out = paged_attention(q, k_pages, v_pages, tables, vl,
                          scale=d ** -0.5, interpret=True)
    ref = paged_attention_ref(q, k_pages, v_pages, tables, vl,
                              scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_auto_interpret_memoized_and_forced_lowering_error():
    """The backend probe resolves once per process (lru_cache), and
    forcing interpret=False where Pallas cannot lower fails loudly
    instead of dying inside Mosaic."""
    from repro.kernels import ops

    ops._backend_is_cpu.cache_clear()
    first = ops._auto_interpret(None)
    before = ops._backend_is_cpu.cache_info().misses
    assert ops._auto_interpret(None) is first
    info = ops._backend_is_cpu.cache_info()
    assert info.misses == before and info.hits >= 1
    assert ops._auto_interpret(True) is True
    if ops._backend_is_cpu():
        with pytest.raises(RuntimeError, match="interpret=False was forced"):
            ops._auto_interpret(False)
    else:
        assert ops._auto_interpret(False) is False


def test_pallas_decode_with_slot_positions_raises():
    """Decode under use_pallas with per-slot positions (what the engine
    passes) raises instead of running the flash kernel with its mask
    dropped; the kernel wrapper takes no explicit mask at all."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import cpu_context, decode_step, init_cache, init_params
    cfg = get_config("gemma-2b").reduced()
    params = init_params(jax.random.key(0), cfg)
    ctx = cpu_context(remat=False, use_pallas=True)
    toks = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(NotImplementedError, match="per-slot valid_len"):
        decode_step(params, toks, init_cache(cfg, 2, 64),
                    jnp.asarray([3, 9], jnp.int32), cfg=cfg, ctx=ctx)
    q = jnp.zeros((1, 8, 2, 64))
    with pytest.raises(TypeError, match="mask"):
        ops.flash_attention(q, q, q, mask=jnp.ones((8, 8), bool))
