"""Mean selector time of the window's rounds (RoundRecord.t_select: host
clock around the selection, ended by block_until_ready)."""


def read(ctx):
    rs = ctx["driver"].rounds
    return sum(r["t_select"] for r in rs) / len(rs) if rs else None
