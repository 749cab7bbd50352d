"""Share of the decoded row-steps that a live request used, in %.

Each request's first token comes from its prefill; every later served
token is one decode step of one row.  The fused loop decodes every row
of the batch at every step, including rows whose request has finished
and rows no request holds."""


def read(ctx):
    j = ctx["job"]
    if not j["decode_row_steps"]:
        return None
    return 100.0 * (j["served_tokens"] - j["requests"]) / j["decode_row_steps"]
