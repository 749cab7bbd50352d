"""Share of its bandwidth roofline that decode attention reaches, in %:
the packed cache bytes of the positions every row holds at every decoded
step (``layer_counts.chunk_cache_bytes``, all KV heads, from the counter,
steps and rows that each ``serve.chunk`` span carries) over the HBM rate
of the chips that share the cache (``chips`` times one chip's), over the
device time of the ops under ``attention`` that run no collective, in
the fused decode loop (``scopes.py``, averaged over the chips)."""
import layer_counts
import scopes


def read(ctx):
    r = scopes.for_ctx(ctx)
    if r is None or not r["chunks"]:
        return None
    s = scopes.scoped_seconds(r, scopes.decode_modules(), "attention")
    if not s:
        return None
    total = sum(layer_counts.chunk_cache_bytes(
        ctx["model"], c["pos"], c["steps"], c["rows"]) for c in r["chunks"])
    hbm = ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * total / hbm / s
