"""Device-idle ms per round inside the program's `repro.chef.update` span
(the DeltaGrad-L constructor: the correction schedule on the host, the
replay and its block_until_ready), on the trace's clock
(bench/program_spans.py)."""
from bench import program_spans


def read(ctx):
    r = program_spans.idle_in(ctx, "repro.chef.update")
    return None if r is None else 1e3 * r[0] / r[1]
