"""Device-idle ms per round inside the program's `repro.chef.commit` span
(the scheduler's commit: `_evaluate`, the round record's host syncs and
`apply_round`), on the trace's clock (bench/program_spans.py)."""
from bench import program_spans


def read(ctx):
    r = program_spans.idle_in(ctx, "repro.chef.commit")
    return None if r is None else 1e3 * r[0] / r[1]
