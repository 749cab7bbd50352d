"""A whole run, less the look for a chip, at a small size on the CPU:
``correct`` holds for the program as it is, and comes out false with the
timed path broken underneath, once for each fault a serving cell can
have, and for the control (the program's own lower-precision path)."""
import jax
import pytest

import run
import tiny
from repro.core import kvcache
from repro.models import lm

CELLS = ["deepseek7b.longdoc_bucket", "starcoder2.chat_bucket"]


def _run(name, seed=2 ** 31 + 3, **kw):
    bench, c = tiny.cell(name)
    return run.run_cell(bench, c, seed, 0.5, False,
                        devices=jax.devices(), log=lambda m: None, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = _run(name)
    assert r["correct"], r["check"]
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert list(r)[-1] == "check"
    assert set(r["metrics"]) == {"output_tok_s", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_token_altered_where_produced(name, monkeypatch):
    real = lm.generate_loop

    def altered(*a, **kw):
        out = real(*a, **kw)
        toks = out["tokens"]
        return dict(out, tokens=toks.at[:, -1].set((toks[:, -1] + 1) % 259))
    monkeypatch.setattr(lm, "generate_loop", altered)
    r = _run(name)
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > tiny.LIMIT


@pytest.mark.parametrize("name", CELLS)
def test_step_returns_its_state_unchanged(name, monkeypatch):
    monkeypatch.setattr(kvcache, "append_token",
                        lambda cache, k, v, legacy=False: cache)
    r = _run(name)
    assert not r["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_norms_and_biases_left_at_ones_and_zeros(name, monkeypatch):
    """The served weights keep the initializer's norm scales of one and
    biases and shifts of zero, while the reference applies the seeded
    ones: the comparison sees the norm and bias paths."""
    monkeypatch.setattr(run, "put_affine", lambda params, affine: params)
    run._init_weights.cache_clear()
    try:
        r = _run(name)
    finally:
        run._init_weights.cache_clear()
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > tiny.LIMIT


@pytest.mark.parametrize("name", CELLS)
def test_control_lower_precision_fails(name):
    """The control: activations at 4 bits (the configuration states 8)."""
    _, c = tiny.cell(name)
    quant = run.quant_config(dict(c["config"]["numerics"], act_bits=4))
    r = _run(name, quant=quant)
    assert not r["correct"]
    assert r["check"]["max_logit_gap"]["value"] > 3 * tiny.LIMIT
