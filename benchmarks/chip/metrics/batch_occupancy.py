"""Share of the decode steps' slots that decoded a token over the window:
Δ``tokens`` ÷ (Δ``steps`` × ``max_batch``) of ``EngineStats``. A slot
still streaming its prompt, or empty, rides a step without a token."""


def read(ctx):
    steps = ctx.stats1.steps - ctx.stats0.steps
    if steps <= 0:
        return None
    slots = steps * ctx.cell.config["engine"]["max_batch"]
    return 100.0 * (ctx.stats1.tokens - ctx.stats0.tokens) / slots
