"""``bench/work.py`` against counts made by hand at small shapes."""
import pytest

from bench import model, work

CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
       "num_hidden_layers": 3, "rms_norm_eps": 1e-5, "rope_theta": 1e4,
       "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
S = model.Sizes(CFG)


def test_params_per_layer():
    # q 8x2x4, k and v 8x1x4 each, o 2x4x8, gate/up 8x16, down 16x8
    assert work.matmul_params_per_layer(S) == 64 + 32 + 32 + 64 + 3 * 128
    assert work.layer_weight_elems(S) == 576 + 16      # two norm gains


def test_forward_and_train_flops():
    # 2 tokens, causal: keys 1 + 2 = 3
    assert work.causal_keys(2) == 3
    per_token = 2 * (3 * 576 + 8 * 10)
    attn = 3 * 4 * 2 * 4 * 3            # layers * 4 * heads * dh * keys
    assert work.forward_flops(S, 2, 3) == per_token * 2 + attn
    assert work.train_step_flops(S, 1, 2) == 3 * (per_token * 2 + attn)


def test_decode_step():
    # 2 active sequences attending 5 keys in all
    flops, nbytes = work.decode_step(S, 2, 5)
    assert flops == work.forward_flops(S, 2, 5)
    weights = 3 * 592 + 8 + 80 + 2 * 8
    kv = 3 * 2 * 1 * 4 * (5 + 2)
    assert nbytes == (weights + kv) * 2 + 2 * 10 * 4


def test_roofline_picks_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(200.0, 10.0, peak) == 2.0
    assert work.roofline_s(100.0, 50.0, peak) == 5.0


def test_reduce_scatter_fold_bytes():
    # p = 4: each rank folds 3 received blocks of n/4 into its own,
    # reading two operands and writing one: 3 * 3/4 * n * 4 bytes.
    assert work.reduce_scatter_fold_bytes(1000, 4, 4) == pytest.approx(9000)
    assert work.reduce_scatter_fold_bytes(1000, 1, 4) == 0
    assert work.zero1_sync_elems([(8, 128), (4,), (2048,)], 4) == 1024 + 2048
