"""Device time per prefill program (the engine's jitted `prefill`), from
the trace's XLA Modules line."""
from bench import readers


def read(ctx):
    evs = readers.module_events(ctx, "jit_prefill")
    return 1e3 * sum(e.dur for e in evs) / len(evs) if evs else None
