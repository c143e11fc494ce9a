"""Device time per decode step program (the engine's jitted
`decode_step`), from the trace's XLA Modules line."""
from bench import readers


def read(ctx):
    evs = readers.module_events(ctx, "jit_decode_step")
    return 1e3 * sum(e.dur for e in evs) / len(evs) if evs else None
