"""Unpadded prompt FLOPs of the prefills completed in the window over
(window x bf16 peak) (bench/counts.py: prefill)."""
from bench import counts, readers


def read(ctx):
    d = ctx["driver"]
    lens = readers.prefills(d, d.t0, d.t_loop_end)
    if not len(lens):
        return None
    f = sum(counts.prefill(ctx["cfg"], int(n)) for n in lens)
    return 100.0 * f / ((d.t_loop_end - d.t0) * ctx["peaks"]["bf16_flops_per_s"])
