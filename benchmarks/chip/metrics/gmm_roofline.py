"""The grouped expert kernels of the decode step against their roofline.

Need: each decode step reads each unique expert's three matrices once
per layer and computes its rows. With one slot the top-k picks are
distinct, so a step needs exactly top_k experts and top_k rows a layer.
Time: the device time of the ``moe_ffn`` Mosaic kernels inside runs of
``_decode_step``. With more than one slot the unique experts of a step
are not counted by the program, so nothing is read.
"""
from benchmarks.chip import work


def read(ctx):
    if ctx.cell.config["engine"]["max_batch"] != 1:
        return None
    tr = ctx.trace
    steps = len(tr.module_runs("jit__decode_step"))
    kernel_s = tr.kernel_seconds("moe_ffn", module="jit__decode_step")
    if steps == 0 or kernel_s <= 0:
        return None
    k = ctx.model.moe.top_k
    flops, nbytes = work.decode_gmm(ctx.model, unique_experts=k, rows=k)
    need = steps * ctx.model.num_layers * work.least_seconds(
        flops, nbytes, ctx.peak)
    return 100.0 * need / kernel_s
