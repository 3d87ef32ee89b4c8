from perfbench.metrics._setup import part


def read(ctx):
    return part(ctx, "warmup")
