from perfbench.metrics._stages import per_fit


def read(ctx):
    placed = per_fit(ctx, "fit.stage.place.bytes")
    return None if placed is None else placed / 1e9
