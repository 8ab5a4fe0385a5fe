"""The plain reference agrees with ``LlamaForCausalLM`` at a tiny
width, and one layer's seeded weights are the whole tree's."""

import jax
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import llama as reference
from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny

ARCH = {"num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0}


@pytest.fixture(scope="module")
def tiny():
    cfg = llama_tiny(use_flash=False)
    model = LlamaForCausalLM(cfg)
    shapes = weights.param_shapes(
        model, {"input_ids": np.zeros((1, 16), np.int32)})
    tree = weights.seeded_tree(shapes, 2 ** 31 + 5, "float32")
    ids = np.random.default_rng(0).integers(0, 256, (2, 40)) \
        .astype(np.int32)
    return model, shapes, tree, ids


def _layer_of(tree):
    return lambda i: tree[f"layers_{i}"]


def test_reference_logits_agree_with_the_trunk(tiny):
    model, _, tree, ids = tiny
    want = model.apply({"params": tree}, {"input_ids": ids},
                       return_logits=True)
    for row in range(2):
        got = reference.logits(ids[row], ARCH, tree, _layer_of(tree))
        assert reference.logit_gap(got, want[row]) < 1e-5


def test_reference_loss_agrees_with_the_trunk(tiny):
    model, _, tree, ids = tiny
    want = float(model.apply({"params": tree}, {"input_ids": ids}))
    got = np.mean([reference.lm_loss(ids[r], ARCH, tree, _layer_of(tree))
                   for r in range(2)])
    assert got == pytest.approx(want, rel=1e-5)


def test_padding_past_the_context_leaves_the_row_alone(tiny):
    _, _, tree, ids = tiny
    exact = reference.next_token_logits(ids[0, :23], 23, ARCH, tree,
                                        _layer_of(tree))
    padded = np.zeros(64, np.int32)
    padded[:23] = ids[0, :23]
    again = reference.next_token_logits(padded, 23, ARCH, tree,
                                        _layer_of(tree))
    assert reference.logit_gap(again, exact) < 1e-5


def test_a_dropped_layer_fails_the_tolerance(tiny):
    from benchmarks.runners.serve import LOGIT_TOL
    _, _, tree, ids = tiny
    full = reference.next_token_logits(ids[0], 40, ARCH, tree,
                                       _layer_of(tree))
    short = reference.next_token_logits(
        ids[0], 40, dict(ARCH, num_hidden_layers=1), tree, _layer_of(tree))
    assert reference.logit_gap(short, full) > LOGIT_TOL


def test_one_layer_regenerates_the_whole_trees_values(tiny):
    _, shapes, tree, _ = tiny
    one = weights.seeded_tree(shapes, 2 ** 31 + 5, "float32",
                              only=("layers_1",))
    assert set(one) == {"layers_1"}
    same = jax.tree.map(lambda a, b: bool(np.array_equal(a, b)),
                        tree["layers_1"], one["layers_1"])
    assert all(jax.tree.leaves(same))


def test_seeds_and_leaves_differ_and_norms_are_one(tiny):
    _, shapes, tree, _ = tiny
    other = weights.seeded_tree(shapes, 6, "float32")
    a = tree["layers_0"]["mlp"]["gate_proj"]["kernel"]
    assert not np.array_equal(a, other["layers_0"]["mlp"]["gate_proj"]
                              ["kernel"])
    assert not np.array_equal(a, tree["layers_0"]["mlp"]["up_proj"]
                              ["kernel"])
    assert not np.array_equal(a, tree["layers_1"]["mlp"]["gate_proj"]
                              ["kernel"])
    assert np.all(np.asarray(tree["norm"]["weight"]) == 1.0)
    assert float(np.std(a)) == pytest.approx(1 / np.sqrt(a.shape[0]),
                                             rel=0.1)
    bf16 = weights.seeded_tree(shapes, 6, "bfloat16", only=("lm_head",))
    assert bf16["lm_head"]["kernel"].dtype == jax.numpy.bfloat16
