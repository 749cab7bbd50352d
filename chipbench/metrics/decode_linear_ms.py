"""Device time of the linear layers in one decode step, in ms: the ops
under the program's ``qlinear`` scope (activation quantization, weight
dequantization and the multiply; on a mesh, not the collectives under
it, which are ``decode_collective_ms``') in the fused decode loop, over
the decode steps the traced job's ``serve.chunk`` spans carry
(``scopes.py``)."""
import scopes


def read(ctx):
    r = scopes.for_ctx(ctx)
    if r is None or not r["chunks"]:
        return None
    s = scopes.scoped_seconds(r, scopes.decode_modules(), "qlinear")
    return None if s is None else 1e3 * s / scopes.decode_steps(r)[0]
