"""The plain reference against the program, at a small size in float32,
where the two agree to rounding: prefill, then decoding through the
packed cache past the 4-bit demotions, for both configurations."""
import jax
import numpy as np
import pytest

import run
import spec
import tiny
import traffic
from loop import play


@pytest.mark.parametrize("name", ["deepseek7b.longdoc_bucket",
                                  "starcoder2.chat_bucket"])
def test_reference_follows_the_served_tokens(name):
    bench, c = tiny.cell(name)
    c["traffic"].update(max_seq=512, prompt_tokens={"min": 160, "max": 160},
                        output_tokens={"min": 100, "max": 200,
                                       "median": 150, "sigma": 0.3})
    seed = 2 ** 31 + 9
    loop = run.build(c["config"], c["traffic"], seed)
    prompts, budgets = traffic.job(c["traffic"], seed)
    job = play(loop, prompts, budgets)
    ref = spec.reference(c["config"]["reference"])
    requests = [(run.prompt_ids(c["traffic"], p), s)
                for p, s in zip(prompts, job["served"])]
    logits = ref.served_logits(c["config"]["model"], c["config"]["numerics"],
                               run.seed_key(seed), requests)
    for lg, (_, s) in zip(logits, requests):
        assert lg.shape == (len(s), c["config"]["model"]["vocab_size"])
        assert (lg.argmax(-1) == np.asarray(s)).mean() > 0.95
        gap = lg.max(-1) - lg[np.arange(len(s)), s]
        assert gap.max() < tiny.LIMIT


@pytest.mark.parametrize("name", ["deepseek7b.longdoc_bucket",
                                  "starcoder2.chat_bucket"])
def test_reference_draws_the_weights_the_program_serves(name):
    from repro.layers.common import weight_dequant
    _, c = tiny.cell(name)
    m = c["config"]["model"]
    key = run.seed_key(4)
    params = run.build(c["config"], c["traffic"], 4).engine.params
    ref = spec.reference("dense")
    mh = tuple(sorted(dict(m, **c["config"]["numerics"]).items()))
    layer = ref._layer_weights(mh, ref._layer_keys(m, key)[1])
    prog = jax.tree.map(lambda a: a[1], params["blocks"]["attn"])
    for name in layer:
        np.testing.assert_allclose(
            np.asarray(weight_dequant(prog[name], np.float32)),
            np.asarray(layer[name]), rtol=0, atol=1e-6)
    affine = ref.affine_params(m, key)
    names = set(affine["layers"])
    assert {"ln1", "ln2"} <= names
    assert ({"bq", "bk", "bv", "b_up", "b_down", "ln1_bias"} <= names) \
        == m["qkv_bias"]
    for name, a in affine["layers"].items():
        assert np.array_equal(np.asarray(prog[name]), np.asarray(a[1]))
        assert float(np.std(np.asarray(a, np.float32))) > 0.05
    for name, a in affine["top"].items():
        assert np.array_equal(np.asarray(params[name]), np.asarray(a))
