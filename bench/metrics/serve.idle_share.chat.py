"""Share of the traced chat window in which no op ran on the device."""
from bench import trace


def read(ctx):
    s = trace.idle_share(ctx["trace"])
    return None if s is None else 100.0 * s
