from perfbench.metrics._common import median


def read(ctx):
    return median(1e3 * (f["t1"] - f["t0"]) for f in ctx.record["fits"])
