"""Least time of the window's lr_hvp kernel calls over their device time.
One call reads Xa [N, d+1] once (bench/counts.py: lr_hvp)."""
from bench import counts, readers


def read(ctx):
    evs = readers.kernel_events(ctx, "lr_hvp")
    c = ctx["cfg"]
    f, b = counts.lr_hvp(c["n_train"], c["feature_dim"] + 1, c["n_classes"])
    least = len(evs) * counts.least_time(f, b, ctx["peaks"])
    return readers.share(least, sum(e.dur for e in evs))
