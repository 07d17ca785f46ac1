"""`references/llama_lean.py` computes what `references/llama.py` does.

The lean reference splits only axes no matmul sums over (weight columns,
query and token blocks, vocabulary rows of the head) and takes every
float8 scale over the summed axis as before, so both references give the
same logits, and the same float8 control logits, up to float32 rounding.
Its blocks are shrunk here so that every matmul, the MLP and attention
each run over several blocks at test widths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check as C
from chipbench import spec as S
from chipbench import weights as W
from chipbench.model import Shape

SEED = 2**31 + 911


def _shape(n_heads, n_kv, vocab):
    return Shape.from_config("lean", {
        "hidden_size": 16 * n_heads, "intermediate_size": 352,
        "num_attention_heads": n_heads, "num_hidden_layers": 2,
        "num_key_value_heads": n_kv, "rms_norm_eps": 1e-5,
        "rope_theta": 5e6, "torch_dtype": "bfloat16", "vocab_size": vocab})


def _packed(m, pack):
    rng = np.random.default_rng(3)
    done = [C.Done(prompt=rng.integers(0, m.vocab, n).tolist(),
                   tokens=rng.integers(0, m.vocab, k).tolist(), model="lean")
            for n, k in ((40, 9), (130, 20), (7, 4), (201, 30))]
    tokens, pos, seg, rows, _ = C.pack_rows(done, pack)
    return [jnp.asarray(x) for x in (tokens, pos, seg, rows)]


# (query heads, KV heads, vocabulary): GQA groups of 7 (Yi-34B's) and 8
@pytest.mark.parametrize("heads", [(14, 2, 400), (16, 2, 384)],
                         ids=["group7", "group8"])
def test_lean_reference_matches_plain_reference(heads):
    m = _shape(*heads)
    plain = S.reference("llama")
    lean = S.reference("llama_lean")
    lean.COLS, lean.T_BLOCK, lean.Q_BLOCK = 48, 128, 64
    key = W.model_key(SEED, m.name)
    args = _packed(m, 512)
    want, want_c = plain.logits(m, key, *args, control=True)
    got, got_c = lean.logits(m, key, *args, control=True)
    want, got = np.asarray(want), np.asarray(got)
    assert got.shape == want.shape == (63, m.vocab)
    assert float(want.std()) > 0.3
    # float32 at Precision.HIGHEST both ways; blocking changes XLA's
    # tiling of each matmul, not its sums, so only rounding differs
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    # the float8 streams round the same operands with the same scales, but
    # a value that lies on a float8 rounding tie in one compiled program
    # may lie an ulp off it in the other, so the two streams are compared
    # by how far each lies from its reference (they read 0.121 and 0.122
    # on average here) and by the gap each reads, which stays far above
    # the reference's rounding in both
    want_c, got_c = np.asarray(want_c), np.asarray(got_c)
    off_want = np.abs(want_c - want).mean()
    off_got = np.abs(got_c - got).mean()
    assert off_got == pytest.approx(off_want, rel=0.1)
    for ctrl, ref in ((want_c, want), (got_c, got)):
        assert C.gaps(ref, ctrl.argmax(1)).max() > 0.1


def test_lean_reference_refuses_a_pack_off_its_query_blocks():
    m = _shape(14, 2, 400)
    lean = S.reference("llama_lean")
    key = W.model_key(SEED, m.name)
    with pytest.raises(ValueError, match="query blocks"):
        lean.logits(m, key, *_packed(m, 500))


def test_lean_reference_compiles_once_per_weight_matrix():
    m = _shape(14, 2, 400)
    m = dataclasses.replace(m, n_layers=3)
    lean = S.reference("llama_lean")
    lean.logits(m, W.model_key(SEED, m.name), *_packed(m, 512))
    # the layer index is traced: one program per matrix, not per layer
    assert lean._weight._cache_size() == len(W.LAYER_KEYS)
    assert lean._attention._cache_size() == lean._mlp._cache_size() == 1
