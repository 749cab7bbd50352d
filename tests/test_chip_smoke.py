"""``chip_smoke.py``'s phases on the CPU at the smoke config.

The script's ``main`` refuses any platform but a TPU; the phases it runs
there are plain functions, driven here through the same entry points
(``Engine.generate``, ``ServeLoop.serve``) at a size the CPU can hold.
"""
import numpy as np
import pytest

import chip_smoke
from repro.configs import get_arch
from repro.serving.engine import ALIGN, ceil_align

SMOKE = get_arch(chip_smoke.LLAMA).smoke
TOKENS = (32, 160)


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "'cpu'" in str(e.value.code)
    assert capsys.readouterr().out == ""          # no result line


def test_seeded_prompts_span_lengths():
    prompts = chip_smoke.seeded_prompts()
    assert prompts == chip_smoke.seeded_prompts()
    lens = sorted(len(p.encode()) + 1 for p in prompts)   # + BOS
    assert lens[0] == chip_smoke.PROMPT_TOKENS[0]
    assert lens[-1] == chip_smoke.PROMPT_TOKENS[1]
    assert len(set(lens)) == chip_smoke.N_PROMPTS


def test_phases_serve_at_smoke_config():
    prompts = chip_smoke.seeded_prompts(tokens=TOKENS)
    max_seq = ceil_align(TOKENS[1]) + 2 * ALIGN
    eng = chip_smoke.build_engine(SMOKE, max_seq=max_seq, max_new=8)
    out = chip_smoke.run_generate(eng, prompts)
    assert out["tokens"].shape == (len(prompts), 8)
    texts = chip_smoke.run_serve_loop(eng, prompts)
    assert len(texts) == len(prompts)


def test_compare_engines_catches_other_weights():
    """The mesh-vs-one-chip comparison passes for identical engines and
    fails when the weights differ (what a wrong shard would look like)."""
    prompts = chip_smoke.seeded_prompts(n=2, tokens=(32, 64))
    ref = chip_smoke.build_engine(SMOKE, max_seq=128, max_new=4)
    same = chip_smoke.build_engine(SMOKE, max_seq=128, max_new=4)
    worst, agree, prefix = chip_smoke.compare_engines(ref, same, prompts,
                                                      steps=4)
    assert worst == 0.0 and agree == 1.0 and prefix == [4, 4]
    other = chip_smoke.build_engine(SMOKE, max_seq=128, max_new=4, seed=1)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_engines(ref, other, prompts, steps=4)


def test_check_tokens_rejects_out_of_vocab():
    eng = type("E", (), {"cfg": SMOKE})()
    bad = np.full((2, 3), SMOKE.vocab_size)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_tokens(eng, bad)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_tokens(eng, np.zeros((2, 3), int),
                                first_expected=np.ones(2, int))
