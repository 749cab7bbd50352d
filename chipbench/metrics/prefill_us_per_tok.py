"""Device time of the prefill executables per real prompt token, in us
(padding is work the prefill does, but not a token it serves)."""


def read(ctx):
    ex = ctx["trace"]["exec"]["prefill"]
    if not ex["count"] or not ctx["job"]["prompt_tokens"]:
        return None
    return 1e6 * ex["seconds"] / ctx["job"]["prompt_tokens"]
