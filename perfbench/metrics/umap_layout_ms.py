from perfbench.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "layout")
