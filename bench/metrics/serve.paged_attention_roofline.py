"""Least time of the paged decode attention over its kernel device time.
The kernel is the custom call inside the decode step program. Its work
over the traced window: for each token a decode step produced, K and V of
that slot's valid context and 4 x Hq x D FLOPs per context position, per
layer (bench/counts.py: paged_attention)."""
from bench import counts, readers


def read(ctx):
    d = ctx["driver"]
    evs = readers.kernels_in(ctx, "jit_decode_step")
    toks = readers.decode_tokens(d, d.t0, d.t_loop_end)
    if not evs or not len(toks):
        return None
    pk = ctx["peaks"]
    f = b = 0
    for n in toks:
        fi, bi = counts.paged_attention(ctx["cfg"], int(n))
        f, b = f + fi, b + bi
    return readers.share(counts.least_time(f, b, pk), sum(e.dur for e in evs))
