"""The served path as the benchmark drives it: ``ServeLoop`` with a record.

``ServeLoop.serve`` returns decoded text, and the byte tokenizer drops
every id >= 256, so neither the served token ids nor their count can be
read from what it returns.  ``RecordingServeLoop`` keeps, for each
request, the ids ``ServeLoop._finalize`` is handed (cut at the budget and
at EOS, as the parent cuts them) and then calls the parent.  It also
counts what the harness's per-layer metrics read: decode steps
dispatched and the prefill tokens the engine ran, padded and real, and
it marks the engine's prefill and decode-chunk dispatches with host
spans in the profiler's trace.
"""
from __future__ import annotations

import jax

from repro.serving.engine import ServeLoop


class RecordingServeLoop(ServeLoop):

    def __init__(self, engine, batch_size: int, max_steps: int):
        super().__init__(engine, batch_size=batch_size, max_steps=max_steps)
        self._reset()
        prefill, fused = engine.prefill, engine._fused

        def traced_prefill(toks):
            B, S = toks.shape
            self.counts["prefills"] += 1
            self.counts["prefill_padded_tokens"] += B * S
            self.shapes.append(("prefill", int(B), int(S)))
            with jax.profiler.TraceAnnotation("prefill"):
                return prefill(toks)

        def traced_fused(num_steps, start, batch=None):
            fn = fused(num_steps, start, batch=batch)

            def run(*args):
                B = int(args[1].shape[0])
                self.counts["chunks"] += 1
                self.counts["decode_steps"] += num_steps
                self.counts["decode_row_steps"] += num_steps * B
                self.shapes.append(("chunk", num_steps, B))
                with jax.profiler.TraceAnnotation("chunk"):
                    return fn(*args)
            return run

        engine.prefill = traced_prefill
        engine._fused = traced_fused

    def _reset(self):
        self.served = {}
        self.shapes = []                # ("prefill", B, S), ("chunk", n, B)
        self.counts = dict.fromkeys(
            ("prefills", "prefill_padded_tokens", "chunks", "decode_steps",
             "decode_row_steps"), 0)

    def serve(self, prompts, max_new_tokens=None):
        self._reset()
        return super().serve(prompts, max_new_tokens)

    def _finalize(self, req, toks, budget, results):
        seq = list(toks[:budget])
        eos = self.engine.tok.eos_id
        if eos in seq:
            seq = seq[: seq.index(eos)]
        self.served[req] = seq
        super()._finalize(req, toks, budget, results)


def play(loop: RecordingServeLoop, prompts, budgets) -> dict:
    """One job through ``loop``: the served ids per request (``None``
    for a request the loop never finalized) and the loop's counts."""
    loop.serve(prompts, list(budgets))
    served = [loop.served.get(i) for i in range(len(prompts))]
    return {"served": served, "counts": dict(loop.counts),
            "shapes": list(loop.shapes), "stats": dict(loop.stats)}
