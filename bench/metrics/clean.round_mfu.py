"""The rounds' least time over their measured wall time. The least time
sums, over the window's kernel calls of each CHEF op, max(FLOPs / bf16
peak, bytes / HBM peak) at the op's shape: lr_hvp and infl_scores over
all N rows, lr_grad over the validation rows, minibatch_grad over a
batch, replay_correction over b rows (an upper bound: a step corrects at
most the round's b rows). Work outside the kernels, such as the XLA
probabilities pass before the scores, is not counted."""
from bench import counts, readers


def read(ctx):
    c, pk, d = ctx["cfg"], ctx["peaks"], ctx["driver"]
    d1, C = c["feature_dim"] + 1, c["n_classes"]
    shapes = {"lr_hvp": counts.lr_hvp(c["n_train"], d1, C),
              "infl_scores": counts.infl_scores(c["n_train"], d1, C),
              "lr_grad": counts.lr_grad(c["n_val"], d1, C),
              "minibatch_grad": counts.minibatch_grad(c["batch_size"], d1, C),
              "replay_correction": counts.replay_correction(c["round_size"], d1, C)}
    least = 0.0
    for op, (f, b) in shapes.items():
        n = len(readers.kernel_events(ctx, op))
        least += n * counts.least_time(f, b, pk)
    took = d.t1 - d.t0
    return readers.share(least, took) if least else None
