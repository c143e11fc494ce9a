"""Median over the window's `ServeEngine.run` calls (`repro.serve.run`
spans) of the device-idle ms inside each: the pool build, and each
admission's prefill, commit and first-token read-back. A median, so that
the rare long pauses of a run() call do not swing it
(bench/program_spans.py)."""
import statistics

from bench import program_spans


def read(ctx):
    each = program_spans.idle_each(ctx, "repro.serve.run")
    return None if not each else 1e3 * statistics.median(each)
