"""1 - prompt tokens / prefilled tokens over the window's run() calls
(ServeEngine.stats): the share of prefill rows that are bucket padding."""


def read(ctx):
    s = ctx["driver"].stats
    if not s.get("prefill_tokens"):
        return None
    return 100.0 * (1.0 - s["prompt_tokens"] / s["prefill_tokens"])
