"""Demand hits of the expert cache per access over the window
(``EngineStats`` hits and accesses). A count: while the expert table
sits in HBM a hit saves no bytes (see PERF.md)."""


def read(ctx):
    acc = ctx.stats1.accesses - ctx.stats0.accesses
    if acc <= 0:
        return None
    return 100.0 * (ctx.stats1.hits - ctx.stats0.hits) / acc
