from perfbench.metrics._stages import TIMED, stage_ms


def read(ctx):
    staged = [stage_ms(ctx, stage) for stage in TIMED]
    if all(ms is None for ms in staged):
        return None  # a program without stage spans: nothing to take off the wall
    fits = ctx.record["fits"]
    wall_ms = sum(f["t1"] - f["t0"] for f in fits) / len(fits) * 1e3
    return wall_ms - sum(ms or 0.0 for ms in staged)
