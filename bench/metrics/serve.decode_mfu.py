"""Model FLOPs of the window's decode steps over (their device time x bf16
peak). A decoded token costs 2 x parameters plus attention over its
context (bench/counts.py: decode_token); the step time is the decode step
program's, from the trace."""
from bench import counts, readers


def read(ctx):
    d = ctx["driver"]
    evs = readers.module_events(ctx, "jit_decode_step")
    toks = readers.decode_tokens(d, d.t0, d.t_loop_end)
    if not evs or not len(toks):
        return None
    f = sum(counts.decode_token(ctx["cfg"], int(n))[0] for n in toks)
    took = sum(e.dur for e in evs) * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * f / took
