"""Model-FLOP counts against hand counts at a small configuration."""
import pytest

import yardstick

SMALL = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 10, "tie_word_embeddings": False}


def _hand_forward(c, batch, seq):
    """Every matrix product of the forward pass, multiply-adds x 2."""
    d, hd = c["hidden_size"], c["head_dim"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer_token = (d * H * hd            # q
                       + d * Hkv * hd        # k
                       + d * Hkv * hd        # v
                       + H * hd * d          # o
                       + 2 * d * c["intermediate_size"]     # gate, up
                       + c["intermediate_size"] * d)        # down
    macs = seq * c["num_hidden_layers"] * per_layer_token
    # causal attention: query i scores keys 0..i, then mixes their values
    for i in range(seq):
        macs += c["num_hidden_layers"] * H * (i + 1) * hd * 2
    macs += seq * d * c["vocab_size"]        # logits; no embedding lookup
    return 2.0 * batch * macs


def test_forward_matches_hand_count():
    assert yardstick.forward_flops(SMALL, 2, 3) == _hand_forward(SMALL, 2, 3)
    assert yardstick.forward_flops(SMALL, 2, 3) == 15552.0


@pytest.mark.parametrize("tied", [False, True])
def test_tied_head_counts_its_product_and_no_lookup(tied):
    c = dict(SMALL, tie_word_embeddings=tied)
    assert yardstick.forward_flops(c, 1, 5) == _hand_forward(SMALL, 1, 5)


def test_gqa_changes_only_kv_projections():
    mha = dict(SMALL, num_key_value_heads=2)
    diff = yardstick.forward_flops(mha, 1, 4) - \
        yardstick.forward_flops(SMALL, 1, 4)
    # one more kv head: its k and v projections, for 4 tokens, 2 layers
    assert diff == 2.0 * 4 * 2 * (2 * SMALL["hidden_size"] * 4)


def test_training_is_three_forwards():
    assert yardstick.train_step_flops(SMALL, 2, 3) == 3 * 15552.0


def test_peaks_refuse_unknown_chip():
    assert yardstick.peaks("TPU v5 lite").flops_bf16 == 197e12
    with pytest.raises(ValueError):
        yardstick.peaks("cpu")
