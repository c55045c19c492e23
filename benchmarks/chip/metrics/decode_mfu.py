"""Model operations of the tokens decoded in the traced window over the
window times the chip's peak. A decoded token attends its prompt, the
tokens before it and itself; first tokens come from prefill and are
counted by prefill_mfu."""
from benchmarks.chip import work


def read(ctx):
    tr = ctx.trace
    flops = 0
    for r in ctx.served:
        for i, t in enumerate(r.times[1:], start=1):
            if tr.a <= t <= tr.b:
                flops += work.decode_token_flops(ctx.model,
                                                 len(r.prompt) + i)
    if flops == 0:
        return None
    return 100.0 * flops / (tr.window_s * ctx.peak["bf16_flops_per_s"])
