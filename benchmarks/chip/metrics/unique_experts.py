"""Mean distinct experts a decode step computes in one layer, over the
window: Δ``unique_experts`` ÷ (Δ``steps`` × layers) of ``EngineStats``.
Nothing is read from a program that does not count them."""


def read(ctx):
    if not hasattr(ctx.stats1, "unique_experts"):
        return None
    steps = ctx.stats1.steps - ctx.stats0.steps
    if steps <= 0:
        return None
    return ((ctx.stats1.unique_experts - ctx.stats0.unique_experts)
            / (steps * ctx.model.num_layers))
