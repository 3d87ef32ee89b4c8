from perfbench.metrics._common import work


def read(ctx):
    start, end = ctx.record["window"]
    flops = work(ctx)["fit_flops"] * len(ctx.record["fits"])
    return 100.0 * flops / (end - start) / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
