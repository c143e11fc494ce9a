"""Device-idle ms per round inside the program's `repro.chef.select` span
(the selection and its block_until_ready: the CG build and solve, the
Increm-INFL prune and its host sync), on the trace's clock
(bench/program_spans.py)."""
from bench import program_spans


def read(ctx):
    r = program_spans.idle_in(ctx, "repro.chef.select")
    return None if r is None else 1e3 * r[0] / r[1]
