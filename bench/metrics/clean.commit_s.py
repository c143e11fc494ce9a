"""Mean round wall time less selection and update: the scheduler's own
work, the annotation vote, `_evaluate` and `apply_round`."""


def read(ctx):
    rs = ctx["driver"].rounds
    if not rs:
        return None
    return sum(r["t_end"] - r["t_start"] - r["t_select"] - r["t_update"]
               for r in rs) / len(rs)
