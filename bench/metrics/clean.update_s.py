"""Mean constructor time of the window's rounds (RoundRecord.t_update: host
clock around the DeltaGrad-L replay, ended by block_until_ready)."""


def read(ctx):
    rs = ctx["driver"].rounds
    return sum(r["t_update"] for r in rs) / len(rs) if rs else None
