"""Start the served path on TPU and check what comes out.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four chips (one host, 2x2)

One chip: the ``harmonia-llama3.1-8b`` engine at its published widths
(32 layers, d_model 4096, GQA 32/8, d_ff 14336, vocab 128256; INT4
weights drawn from a seed and packed layer by layer; ``max_seq`` 2048)
serves 8 seeded byte prompts of 32-480 tokens through ``Engine.generate``
and then through ``ServeLoop.serve`` at batch 4.  Every prefill logit must
be finite, every token inside the vocabulary, and the first generated
token the argmax of the prefill logits.

Four chips, and nothing else: Qwen2.5-32B, which no single chip holds,
served on a (data=1, model=4) mesh; then Llama-3.1-8B on that mesh
against the same model on one chip of the host, both in this process
(prefill and 15 teacher-forced decode steps with logits within
``LOGIT_RTOL``; greedy tokens compared and reported — see
``compare_engines``).

Everything runs in this one process, which owns the chip(s).  The
script exits non-zero and prints no result unless JAX's first device is
a TPU.  Compiled programs are cached in ``$JAX_COMPILATION_CACHE_DIR``,
or else in ``.jax_cache/`` at the root of the checkout.  Lines before
the last are set-up information (compile seconds, tokens/s after a
warm-up), not metrics; the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.launch.device import enable_compile_cache, require_tpu  # noqa
from repro.models.init import init_packed_params  # noqa: E402
from repro.serving.engine import Engine, EngineConfig, ServeLoop  # noqa

LLAMA = "harmonia-llama3.1-8b"
QWEN = "qwen2.5-32b"
MAX_SEQ = 2048
N_PROMPTS = 8
PROMPT_TOKENS = (32, 480)
MAX_NEW = 16
SERVE_BATCH = 4
# Mesh vs one chip, as RMS(logit difference) / std(one-chip logits).
# The row-parallel projections sum partial products over 4 shards in
# another order, in bf16, and BFP truncation turns some of those rounding
# differences into whole quantization steps (1/128-1/64 of a group's
# absmax at 8 bits, 1/8-1/4 at 4 bits).  At the smoke config on 4 CPU
# devices this measured 0.042 in bf16 (0.0008 with BFP off, in f32);
# depth compounds it.  Logits that share nothing measure ~1.4, and a
# quarter of the vocabulary wrong ~0.7.
LOGIT_RTOL = 0.25


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def seeded_prompts(n: int = N_PROMPTS, tokens=PROMPT_TOKENS):
    """``n`` printable-ASCII prompts whose token counts (BOS included)
    spread evenly over ``tokens``, in seeded order."""
    rng = np.random.default_rng(0)
    lens = np.linspace(tokens[0], tokens[1], n).round().astype(int)
    rng.shuffle(lens)
    return ["".join(map(chr, rng.integers(32, 127, int(L) - 1)))
            for L in lens]


def build_engine(cfg, max_seq: int = MAX_SEQ, max_new: int = MAX_NEW,
                 mesh=None, seed: int = 0):
    """Engine over INT4 weights drawn and packed layer by layer."""
    t0 = time.perf_counter()
    params = init_packed_params(cfg, jax.random.PRNGKey(seed), mesh=mesh)
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"{cfg.name}: {nbytes} packed parameter bytes, drawn and packed "
        f"in {time.perf_counter() - t0:.1f} s")
    return Engine(params, cfg, EngineConfig(
        max_seq=max_seq, max_new_tokens=max_new, mesh=mesh))


def prefill_logits(eng: Engine, prompts):
    """Host copy of the prefill logits of ``prompts``, checked finite."""
    toks, _ = eng._prepare(prompts)
    logits, caches = eng.prefill(toks)
    logits = np.asarray(logits)
    del caches
    check(logits.shape == (len(prompts), eng.cfg.vocab_size),
          f"prefill logits shape {logits.shape}")
    check(np.isfinite(logits).all(), "non-finite prefill logits")
    return logits


def check_tokens(eng: Engine, tokens, first_expected=None):
    vocab = eng.cfg.vocab_size
    check(((tokens >= 0) & (tokens < vocab)).all(),
          f"tokens outside [0, {vocab})")
    if first_expected is not None:
        check((tokens[:, 0] == first_expected).all(),
              f"first tokens {tokens[:, 0]} are not the prefill argmax "
              f"{first_expected}")


def run_generate(eng: Engine, prompts):
    """``Engine.generate`` twice: a warm-up that compiles, then a run
    whose tokens/s is reported as set-up information."""
    t0 = time.perf_counter()
    first = prefill_logits(eng, prompts).argmax(-1)
    warm = eng.generate(prompts)
    warm_s = time.perf_counter() - t0
    check_tokens(eng, warm["tokens"], first)
    out = eng.generate(prompts)
    check((out["tokens"] == warm["tokens"]).all(),
          "greedy generation differs between two identical calls")
    log(f"Engine.generate: {out['tokens'].shape[0]} prompts x "
        f"{out['tokens'].shape[1]} tokens; warm-up {warm_s:.1f} s "
        f"(compile {warm_s - out['wall_s']:.1f} s), then "
        f"{out['tokens_per_s']:.1f} tok/s (set-up information, not a "
        f"metric)")
    return out


def run_serve_loop(eng: Engine, prompts):
    loop = ServeLoop(eng, batch_size=SERVE_BATCH)
    t0 = time.perf_counter()
    texts = loop.serve(prompts)
    check(len(texts) == len(prompts)
          and all(isinstance(t, str) for t in texts),
          "ServeLoop left requests unanswered")
    check(loop.stats["chunks"] >= 1, f"ServeLoop stats {loop.stats}")
    check(all(len(r.tokens) >= 1 for r in loop.records),
          "ServeLoop served a request no token")
    first = [r.first_token - t0 for r in loop.records]
    done = [r.finished - t0 for r in loop.records]
    log(f"ServeLoop.serve: {len(texts)} requests at batch {SERVE_BATCH}, "
        f"{loop.stats}, {time.perf_counter() - t0:.1f} s (compile "
        f"included); first tokens at {min(first):.1f}-{max(first):.1f} s, "
        f"requests done at {min(done):.1f}-{max(done):.1f} s")
    return texts


def peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return [s.get("peak_bytes_in_use") for s in stats]


def compare_engines(ref: Engine, test: Engine, prompts,
                    steps: int = MAX_NEW):
    """``test`` against ``ref`` on the same prompts.

    Teacher-forced: prefill and ``steps - 1`` decode steps, both engines
    fed ``ref``'s greedy tokens.  At every step and on every row the RMS
    of the logit difference must stay within ``LOGIT_RTOL`` of the std
    of ``ref``'s logits.  Greedy tokens are compared and
    reported, not bounded: where the top two logits lie closer than the
    difference, either engine may rightly pick either.

    Returns (worst relative RMS difference, share of teacher-forced steps
    whose argmax agrees, per row the number of leading tokens on which
    the two engines' free-running greedy ``Engine.generate`` agree)."""
    toks, pad = ref._prepare(prompts)
    lr, cr = ref.prefill(toks)
    lt, ct = test.prefill(toks)
    worst, agree = 0.0, 0
    for step in range(steps):
        a = np.asarray(lr, np.float64)
        b = np.asarray(lt, np.float64)
        check(np.isfinite(b).all(), f"step {step}: non-finite logits")
        rel = np.sqrt(((a - b) ** 2).mean(-1)) / a.std(-1)
        worst = max(worst, float(rel.max()))
        check(rel.max() <= LOGIT_RTOL, f"step {step}: logits differ by an "
              f"RMS of {rel.max():.3g} of their std (> {LOGIT_RTOL})")
        tok = a.argmax(-1)
        agree += int((b.argmax(-1) == tok).sum())
        if step + 1 < steps:
            t = jnp.asarray(tok.astype(np.int32))
            lr, cr = ref.decode(t, cr, pad)
            lt, ct = test.decode(t, ct, pad)
    del cr, ct
    gen = [eng.generate(prompts, max_new_tokens=steps)["tokens"]
           for eng in (ref, test)]
    same = gen[0] == gen[1]
    prefix = [int(r.argmin()) if not r.all() else steps for r in same]
    return worst, agree / (steps * len(prompts)), prefix


def one_chip():
    devices = require_tpu(1)
    cache = enable_compile_cache()
    d = devices[0]
    log(f"device {d.platform} {d.device_kind} x {len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache}")
    cfg = get_arch(LLAMA).config
    prompts = seeded_prompts()
    eng = build_engine(cfg)
    run_generate(eng, prompts)
    run_serve_loop(eng, prompts)
    log(f"peak_bytes_in_use {peak_bytes([d])[0]}")
    return d


def four_chips():
    from jax.sharding import Mesh
    devices = require_tpu(4)
    cache = enable_compile_cache()
    log(f"devices {devices[0].platform} {devices[0].device_kind} x "
        f"{len(jax.devices())}; jax {jax.__version__}; compile cache "
        f"{cache}")
    mesh = Mesh(np.asarray(devices).reshape(1, 4), ("data", "model"))
    prompts = seeded_prompts()

    eng = build_engine(get_arch(QWEN).config, mesh=mesh)
    first = prefill_logits(eng, prompts).argmax(-1)
    out = eng.generate(prompts)
    check_tokens(eng, out["tokens"], first)
    log(f"{QWEN} on (data=1, model=4): {out['tokens'].shape} tokens, "
        f"per-device peak_bytes_in_use {peak_bytes(devices)}")
    del eng, out
    gc.collect()

    cfg = get_arch(LLAMA).config
    sharded = build_engine(cfg, mesh=mesh)
    single = build_engine(cfg)
    worst, agree, prefix = compare_engines(single, sharded, prompts)
    log(f"{LLAMA}: (data=1, model=4) vs one chip over prefill + "
        f"{MAX_NEW - 1} decode steps: logit RMS difference at most "
        f"{worst:.4g} of their std (bound {LOGIT_RTOL}); teacher-forced "
        f"greedy tokens agree at {agree:.3f} of steps; free-running "
        f"Engine.generate agrees on the first {prefix} tokens per row")
    return devices[0]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    d = four_chips() if args.chips == 4 else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
