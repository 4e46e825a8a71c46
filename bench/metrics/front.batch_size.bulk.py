"""Requests per micro-batch the front door formed in the window
(FrontStats: submitted / batches)."""


def read(ctx):
    batches = ctx.stats1["batches"] - ctx.stats0["batches"]
    if batches <= 0:
        return None
    return (ctx.stats1["submitted"] - ctx.stats0["submitted"]) / batches
