"""Serving engine: continuous batching, slot reuse, per-slot positions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import cpu_context, decode_step, init_cache, init_params, prefill
from repro.serving import ServingEngine, sample_tokens

CFG = get_config("gemma-2b").reduced(n_layers=2, d_model=128, vocab_size=512)


def _params():
    return init_params(jax.random.key(0), CFG)


def test_engine_completes_all_requests():
    eng = ServingEngine(CFG, _params(), max_batch=3, max_seq=128)
    _reqs = [eng.submit(list(range(5, 12 + i)), max_new_tokens=6)
             for i in range(7)]
    done = eng.run_until_idle()
    assert len(done) == 7
    assert all(len(r.generated) == 6 for r in done)


def test_engine_greedy_matches_manual_decode():
    """One request through the engine == manual prefill+decode loop."""
    params = _params()
    prompt = [3, 5, 7, 9, 11]
    eng = ServingEngine(CFG, params, max_batch=2, max_seq=64)
    req = eng.submit(prompt, max_new_tokens=4)
    eng.run_until_idle()

    ctx = cpu_context()
    cache = init_cache(CFG, 1, 64)
    tok = jnp.asarray(prompt, jnp.int32)[None]
    last, cache = prefill(params, {"tokens": tok}, cache, cfg=CFG, ctx=ctx)
    manual = [int(jnp.argmax(last[0]))]
    pos = len(prompt)
    for _ in range(3):
        logits, cache = decode_step(
            params, jnp.asarray([[manual[-1]]], jnp.int32), cache,
            jnp.int32(pos), cfg=CFG, ctx=ctx)
        manual.append(int(jnp.argmax(logits[0])))
        pos += 1
    assert req.generated == manual


def test_engine_slot_reuse():
    eng = ServingEngine(CFG, _params(), max_batch=2, max_seq=64)
    for i in range(5):
        eng.submit([1, 2, 3, 4 + i], max_new_tokens=3)
    done = eng.run_until_idle()
    assert len(done) == 5
    slots = {r.slot for r in done}
    assert slots <= {0, 1}          # only 2 slots existed


def test_eos_stops_generation():
    params = _params()
    # find the greedy first token, then use it as "EOS"
    eng0 = ServingEngine(CFG, params, max_batch=1, max_seq=64)
    r0 = eng0.submit([5, 6, 7], max_new_tokens=4)
    eng0.run_until_idle()
    eos = r0.generated[0]
    eng = ServingEngine(CFG, params, max_batch=1, max_seq=64)
    r = eng.submit([5, 6, 7], max_new_tokens=10, eos_id=eos)
    eng.run_until_idle()
    assert r.generated == [eos]


def test_sampling_modes():
    key = jax.random.key(0)
    logits = jnp.array([[0.0, 5.0, 0.0, 0.0]])
    assert int(sample_tokens(key, logits, temperature=0.0)[0]) == 1
    # top-k=1 == greedy even with temperature
    assert int(sample_tokens(key, logits, temperature=1.0, top_k=1)[0]) == 1
    # high temperature explores
    draws = {int(sample_tokens(jax.random.key(i), logits,
                               temperature=50.0)[0]) for i in range(40)}
    assert len(draws) > 1


def test_engine_compile_counters():
    """decode compiles exactly once per (batch, 1) token shape and
    prefill once per pow-2 seq bucket — warm shapes never re-count."""
    eng = ServingEngine(CFG, _params(), max_batch=2, max_seq=64)
    assert eng.decode_compiles == 0 and eng.prefill_compiles == 0
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)        # bucket 8
    eng.submit([9, 8, 7, 6, 5, 4, 3], max_new_tokens=3)  # bucket 8 too
    eng.run_until_idle()
    assert eng.prefill_compiles == 1
    assert eng.decode_compiles == 1
    eng.submit(list(range(1, 21)), max_new_tokens=3)     # bucket 32
    eng.run_until_idle()
    assert eng.prefill_compiles == 2
    assert eng.decode_compiles == 1
    eng.submit([2, 4, 6], max_new_tokens=3)              # bucket 4: new
    eng.submit([3, 5, 7], max_new_tokens=3)              # bucket 4: warm
    eng.run_until_idle()
    assert eng.prefill_compiles == 3
    assert eng.decode_compiles == 1
    # the counters mirror jit's own shape-keyed cache when it exposes one
    if hasattr(eng._decode, "_cache_size"):
        assert eng._decode._cache_size() == eng.decode_compiles
        assert eng._admit_prompt._cache_size() == eng.prefill_compiles


def test_prefix_hit_decode_compile_counted_once():
    """The prefix-hit suffix prefill runs through the batch-1 decode jit:
    one extra decode shape the first time, none after."""
    eng = ServingEngine(CFG, _params(), max_batch=2, max_seq=128,
                        paged=True, kv_block_tokens=16)
    shared = list(range(100, 132))          # 32 tokens = 2 full blocks
    eng.submit(shared + [7, 8, 9], max_new_tokens=3)
    eng.run_until_idle()
    d0 = eng.decode_compiles
    eng.submit(shared + [10, 11, 12], max_new_tokens=3)
    eng.run_until_idle()
    assert eng.n_prefix_hits == 1
    assert eng.decode_compiles == d0 + 1
    eng.submit(shared + [13, 14], max_new_tokens=3)
    eng.run_until_idle()
    assert eng.n_prefix_hits == 2
    assert eng.decode_compiles == d0 + 1


# ---------------------------------------------------------------------------
# one-program admission: the same results as the eager sequence it replaced
# ---------------------------------------------------------------------------


def _eager_admission(eng, prompt, slot, key, cache, pools, table):
    """The admission as a sequence of separate ops, as the engine ran it
    before it became one program: the prefill program, the first token
    sampled eagerly, then `store_prefix`'s per-leaf scatters of the full
    blocks past the read-shared head and `_insert_slot`'s per-leaf slot
    update. Returns (token, batch cache, pools)."""
    plen = len(prompt)
    bucket = min(1 << max(plen - 1, 1).bit_length(), eng.max_seq)
    tokens = jnp.asarray([prompt + [0] * (bucket - plen)], jnp.int32)
    batch = {"tokens": tokens}
    if eng.cfg.mrope:
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(bucket, dtype=jnp.int32), (3, 1, bucket))
    logits, one = jax.jit(eng._prefill_cache)(eng.params, batch,
                                              jnp.int32(plen - 1))
    _, k = jax.random.split(key)
    tok = int(sample_tokens(k, logits, eng.temperature)[0])
    pools = list(pools)
    if table is not None:
        bt = eng.kv.block_tokens
        n_full = min(len(table.blocks), plen // bt)
        skip = table.shared_blocks
        ids = jnp.asarray(table.blocks[skip:n_full], jnp.int32)
        for i, leaf in enumerate(jax.tree.leaves(one)):
            if eng.kv._seq_axis[i] is None or n_full <= skip:
                continue
            lead = jnp.moveaxis(leaf, eng.kv._seq_axis[i], 0)
            lead = lead[skip * bt:n_full * bt]
            pools[i] = jnp.asarray(pools[i]).at[ids].set(
                lead.reshape((n_full - skip, bt) + lead.shape[1:]))
    cache = jax.tree.map(
        lambda c, o, ax: jax.lax.dynamic_update_slice_in_dim(c, o, slot, ax),
        cache, one, eng._axis)
    return tok, cache, pools


def _assert_same(got, want):
    assert len(jax.tree.leaves(got)) == len(jax.tree.leaves(want))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def _admit_checked(eng, prompt, max_new_tokens=4):
    """Admit `prompt` (a fresh prompt: no prefix hit) through the engine's
    one-program admission and check the first token, the whole batch
    cache and every page pool against `_eager_admission`."""
    slot = eng.slot_req.index(None)
    key, hits, fused = eng._key, eng.n_prefix_hits, eng.fused_admissions
    # copies: the engine donates these buffers to the program
    cache = jax.tree.map(np.array, eng.cache)
    pools = [None if p is None else np.array(p)
             for p in eng.kv.pools] if eng.paged else []
    req = eng.submit(prompt, max_new_tokens=max_new_tokens)
    eng._admit()
    assert req.slot == slot and eng.n_prefix_hits == hits
    assert eng.fused_admissions == fused + 1
    tok, want_cache, want_pools = _eager_admission(
        eng, prompt, slot, key, cache, pools,
        req.pages if eng._sharing else None)
    assert req.generated[0] == tok
    _assert_same(eng.cache, want_cache)
    if eng.paged:
        _assert_same(eng.kv.pools, want_pools)
    return req


_MODES = {"dense": dict(), "paged": dict(paged=True),
          "paged-unshared": dict(paged=True, prefix_sharing=False),
          "paged-sampled": dict(paged=True, temperature=1.0, seed=3)}


@pytest.fixture(scope="module", params=list(_MODES))
def resident_engine(request):
    """An engine of each mode with a long request resident in slot 0, so
    the admissions into slot 1 must leave its row alone."""
    eng = ServingEngine(CFG, _params(), max_batch=2, max_seq=64,
                        **_MODES[request.param])
    eng.submit(list(range(400, 420)), max_new_tokens=40)
    eng.step()
    return eng


@pytest.mark.parametrize("plen", [1, 15, 16, 17, 31, 33])
def test_fused_admission_matches_eager_sequence(resident_engine, plen):
    """Token, batch cache (the new slot's row written, the resident's left
    alone) and published pages equal the eager sequence's, for prompts
    around the 16-token block and the pow-2 buckets."""
    eng = resident_engine
    prompt = np.random.default_rng(plen).integers(
        1, CFG.vocab_size, plen).tolist()
    req = _admit_checked(eng, prompt)
    if eng._sharing:
        # the index now serves the full blocks from the table's pages
        assert eng.kv.match_prefix(prompt + [0]) \
            == req.pages.blocks[:plen // 16]
    eng.evict(req.slot, keep_kv=False)


def test_fused_admission_compiles_once_per_bucket():
    """Fresh prompts of 1-33 tokens compile one admission program per pow-2
    bucket (2, 16, 32, 64), and every one of them is a fused admission;
    a prefix hit afterwards is a prefill but not a fused admission."""
    eng = ServingEngine(CFG, _params(), max_batch=2, max_seq=64, paged=True)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, CFG.vocab_size, n).tolist()
               for n in (1, 15, 16, 17, 31, 33)]
    for p in prompts:
        eng.submit(p, max_new_tokens=3)
    eng.run_until_idle()
    assert eng.prefill_compiles == 4
    assert eng._admit_prompt._cache_size() == 4
    assert eng.fused_admissions == eng.n_prefills == 6
    assert eng.n_prefix_hits == 0
    eng.submit(prompts[-1][:32] + [5, 6], max_new_tokens=3)
    eng.run_until_idle()
    assert eng.n_prefix_hits == 1 and eng.n_prefills == 7
    assert eng.fused_admissions == 6 and eng.prefill_compiles == 4


# one reduced config per cache family: dense MQA, GQA, MLA, MoE (with a
# leading dense layer), mrope, rolling window, SSM, hybrid recurrent
_FAMILIES = ["gemma-2b", "yi-6b", "minicpm3-4b", "mixtral-8x7b",
             "deepseek-moe-16b", "qwen2-vl-2b", "gemma3-12b", "mamba2-2.7b",
             "recurrentgemma-2b"]


@pytest.mark.parametrize("arch", _FAMILIES)
def test_fused_admission_each_cache_family(arch):
    """Every cache family admits through the one program (the pools it
    publishes to decided by the probed sequence axes) and decodes on."""
    cfg = get_config(arch).reduced()
    eng = ServingEngine(cfg, init_params(jax.random.key(0), cfg),
                        max_batch=2, max_seq=128, paged=True)
    prompt = np.random.default_rng(1).integers(1, cfg.vocab_size,
                                               20).tolist()
    req = _admit_checked(eng, prompt)
    eng.run_until_idle()
    assert len(req.generated) == 4
    assert eng.fused_admissions == eng.n_prefills == 1


# ---------------------------------------------------------------------------
# parity with a plain float32 reference at a GQA group of 7 (Yi-34B's 56
# query heads over 8 KV heads) and of 8
# ---------------------------------------------------------------------------

def _reference_modules():
    """The benchmark's plain float32 reference (`references/llama.py`), its
    seeded weights and the adapter that hands them to the program."""
    import importlib.util
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                         "chip")
    sys.path.insert(0, os.path.abspath(bench))
    from chipbench import check, fleet, model, weights
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_llama",
        os.path.join(bench, "references", "llama.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref, check, fleet, model, weights


# (query heads, KV heads, dtype, tolerance on |logit - reference logit|).
# float32: the program and the reference differ only in summation order
# and the masking constant; they read 5e-6 on logits of order 1.
# bfloat16 (the served dtype): every activation is rounded to 8 bits of
# mantissa; they read 0.04 after two layers, while the reference's float8
# control (one precision below) misses the same logits by 0.6.
_GQA = {"group7-f32": (14, 2, "float32", 2e-4),
        "group8-f32": (16, 2, "float32", 2e-4),
        "group7-bf16": (14, 2, "bfloat16", 0.15),
        "group8-bf16": (16, 2, "bfloat16", 0.15)}


@pytest.mark.parametrize("case", list(_GQA))
def test_paged_engine_matches_float32_reference_at_gqa_group(case):
    """A Yi-shaped model at test widths (heads of 16, 2 KV heads; GQA group
    7 as Yi-34B's, or 8) on a paged engine: the first token's logits from
    the engine's prefill, then every decode step's logits with the lanes at
    different positions, against the plain float32 reference's full
    forward over the prompt and the served tokens."""
    ref, check, fleet, model, weights = _reference_modules()
    n_heads, n_kv, dtype, tol = _GQA[case]
    m = model.Shape.from_config("gqa", {
        "hidden_size": 16 * n_heads, "intermediate_size": 512,
        "num_attention_heads": n_heads, "num_hidden_layers": 2,
        "num_key_value_heads": n_kv, "rms_norm_eps": 1e-5,
        "rope_theta": 5e6, "torch_dtype": dtype, "vocab_size": 384})
    cfg = fleet.program_config(m, "yi-34b", "test")
    seed = 2**31 + 77
    params = fleet.make_params(seed, m, cfg, jax.devices()[0])
    eng = ServingEngine(cfg, params, max_batch=3, max_seq=64, paged=True,
                        kv_blocks=12, temperature=0.0)
    steps = []
    decode = eng._decode

    def recorded(p, t, c, pos):
        rids = [r.rid if r is not None else -1 for r in eng.slot_req]
        logits, c = decode(p, t, c, pos)
        steps.append((rids, np.asarray(pos), np.asarray(logits, np.float32)))
        return logits, c
    eng._decode = recorded
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, m.vocab, n).tolist() for n in (5, 17, 30)]
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()

    done = [check.Done(prompt=r.prompt, tokens=r.generated, model="gqa")
            for r in reqs]
    tokens, pos, seg, rows, _ = check.pack_rows(done, 512)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.logits(m, weights.model_key(seed, "gqa"),
                             jnp.asarray(tokens), jnp.asarray(pos),
                             jnp.asarray(seg), jnp.asarray(rows))
    want = np.asarray(want)
    first, k = {}, 0
    for r in reqs:
        first[r.rid] = k
        k += len(r.generated)
    worst = 0.0
    for r in reqs:
        # the first token: the engine's prefill at its pow-2 bucket
        plen = len(r.prompt)
        bucket = 1 << max(plen - 1, 1).bit_length()
        got, _ = jax.jit(eng._prefill_cache)(
            eng.params, {"tokens": jnp.asarray(
                [r.prompt + [0] * (bucket - plen)], jnp.int32)},
            jnp.int32(plen - 1))
        worst = max(worst, float(np.abs(
            np.asarray(got[0], np.float32) - want[first[r.rid]]).max()))
    n_decoded = 0
    for rids, p, logits in steps:
        for slot, rid in enumerate(rids):
            if rid < 0:
                continue
            r = next(q for q in reqs if q.rid == rid)
            i = int(p[slot]) - len(r.prompt) + 1     # token it predicts
            worst = max(worst, float(np.abs(
                logits[slot] - want[first[rid] + i]).max()))
            n_decoded += 1
    assert n_decoded == sum(len(r.generated) - 1 for r in reqs)
    # the logits span several units, so the tolerance is a small share
    assert float(want.std()) > 0.3
    assert worst <= tol, worst
