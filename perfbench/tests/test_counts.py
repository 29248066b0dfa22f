"""The FLOP and byte counters against hand counts, and the peaks table."""

import json
from pathlib import Path

import jax
import pytest

from perfbench import counts as C
from perfbench.peaks import peak

# d=8, 2 heads of 4, 1 kv head, ff 16, 2 layers, vocab 10, tied
TINY = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 2,
        "vocab_size": 10, "tie_word_embeddings": True}
# per layer: q,o 8*8 each; k,v 8*4 each; gate, up, down 8*16 each
LAYER = 8 * 8 * 2 + 8 * 4 * 2 + 3 * 8 * 16           # 576
HEAD = 8 * 10                                        # 80
ATT = 4 * 2 * 2 * 4          # FLOPs per (query, key) pair over 2 layers


def test_layer_and_head_params():
    assert C.layer_matmul_params(TINY) == LAYER == 576
    assert C.matmul_params(TINY) == 2 * LAYER + HEAD == 1232


def test_train_step_flops_hand_count():
    # 1 row of 3 tokens: 6 causal pairs; forward x3 for the backward
    fwd = 2 * 1232 * 3 + ATT * 6
    assert C.train_step_flops(TINY, 1, 3) == 3 * fwd == 23328


def test_lars_update_bytes_hand_count():
    # norms: read w, g; apply: read g, m, w, write m, w (f32); + bf16 params
    assert C.lars_update_bytes(100, 90) == 4 * 100 * 7 + 2 * 90 == 2980


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peak("TPU v99")


def test_v5e_peaks_as_published():
    pk = peak("TPU v5 lite")
    assert (pk["bf16_flops"], pk["int8_ops"], pk["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)
    assert pk["ici_bytes_per_s"] * 8 == 1600e9


@pytest.mark.parametrize("extra", [{}, {"qk_norm": True,
                                         "tie_word_embeddings": False}],
                         ids=["smollm-135m", "qk-untied"])
def test_param_count_matches_the_program(extra):
    from perfbench.program import model_config
    from repro.models import build_model
    cfg = json.loads((Path(__file__).parents[1] / "configs" /
                      "smollm-135m.json").read_text())
    cfg.update(extra)
    shapes = jax.eval_shape(build_model(model_config(cfg)).init,
                            jax.random.key(0))
    assert C.param_count(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes))
