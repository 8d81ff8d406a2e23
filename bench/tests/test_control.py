"""The control, at a size the CPU holds: the reference computed one
precision below the configuration's, put in the program's place, has to
fail the cell's own limit (float32 stencil in bfloat16; bfloat16 model in
fp8)."""

import numpy as np

import harness
from conftest import tiny_serving, tiny_stencil


def test_stencil_control_fails():
    parts = tiny_stencil(harness.cell("stencil7-l512-loop"))
    cfg = dict(parts["config"], shape=[16, 64, 128])
    ref = harness.load_module("refs", cfg["reference"])
    inputs = ref.make_inputs(cfg, harness.seed_key(2 ** 31 + 1))
    got = ref.compare(cfg, inputs, ref.control(cfg, inputs))
    limit = parts["cell"]["limits"]["stencil_rel_err"]
    assert got["stencil_rel_err"] > 10 * limit


def test_serving_control_fails():
    # the cell's widths (logits of the cell's scale), two layers and a
    # slice of 8192 rows of the vocabulary, so the CPU holds it
    parts = harness.cell("granite-chat-steady")
    cfg = dict(parts["config"], num_hidden_layers=2, vocab_size=8192)
    ref = harness.load_module("refs", cfg["reference"])
    rng = np.random.default_rng(0)
    key = harness.seed_key(2 ** 31 + 3)
    prompt = rng.integers(0, cfg["vocab_size"], 100)
    served = rng.integers(0, cfg["vocab_size"], 200).tolist()
    _, gaps = ref.control_gaps(key, cfg, prompt, served, 512)
    assert gaps.max() > parts["cell"]["limits"]["logit_gap"]


def test_stacked_weights_equal_layer_by_layer():
    parts = tiny_serving(harness.cell("granite-chat-steady"))
    ref = harness.load_module("refs", "dense_gqa")
    w = ref.widths(parts["config"])
    key = harness.seed_key(12345)
    stacked = ref.stacked_layers(key, w)
    for layer in range(w["layers"]):
        one = ref.layer_weights(key, w, layer)
        for name, leaf in one.items():
            assert np.array_equal(np.asarray(stacked[name][layer]),
                                  np.asarray(leaf)), name
