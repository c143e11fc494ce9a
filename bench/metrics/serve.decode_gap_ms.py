"""Device-idle ms per decode round inside the engine's
`repro.serve.decode_round` span (the decode step, the token read-back,
token bookkeeping, slot release and the admissions made inside the loop),
on the trace's clock (bench/program_spans.py)."""
from bench import program_spans


def read(ctx):
    r = program_spans.idle_in(ctx, "repro.serve.decode_round")
    return None if r is None else 1e3 * r[0] / r[1]
