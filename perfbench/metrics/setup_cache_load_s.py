from perfbench.metrics._program_load import setup_s


def read(ctx):
    return setup_s(ctx, "cache_load")
