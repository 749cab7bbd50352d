"""Share of the prefilled positions that are padding, in %: the tokens
dispatched to prefill (B x S of every prefill, swap-ins included) less
the real prompt tokens, over the tokens dispatched, as the traced job's
``serve.prefill`` spans carry them (``padded`` and ``tokens``: the same
increments as ``ServeLoop.stats``' ``prefill_padded_tokens`` and
``prefill_tokens``; ``scopes.py``)."""
import scopes


def read(ctx):
    r = scopes.for_ctx(ctx)
    if r is None or not r["prefills"]:
        return None
    padded = sum(p["padded"] for p in r["prefills"])
    real = sum(p["tokens"] for p in r["prefills"])
    return 100.0 * (padded - real) / padded if padded else None
