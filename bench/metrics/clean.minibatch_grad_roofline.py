"""Least time of the window's minibatch_grad kernel calls (the DeltaGrad-L
replay's explicit steps: a gathered batch of `batch_size` rows) over their
device time."""
from bench import counts, readers


def read(ctx):
    evs = readers.kernel_events(ctx, "minibatch_grad")
    c = ctx["cfg"]
    f, b = counts.minibatch_grad(c["batch_size"], c["feature_dim"] + 1,
                                 c["n_classes"])
    least = len(evs) * counts.least_time(f, b, ctx["peaks"])
    return readers.share(least, sum(e.dur for e in evs))
