from perfbench.metrics._stages import per_fit


def read(ctx):
    return per_fit(ctx, "umap.layout.epochs")
