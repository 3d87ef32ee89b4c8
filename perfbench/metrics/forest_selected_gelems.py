from perfbench.metrics._stages import per_fit


def read(ctx):
    selected = per_fit(ctx, "forest.grow.selected_elems")
    return None if selected is None else selected / 1e9
