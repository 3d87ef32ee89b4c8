from perfbench.metrics._program_load import setup_total


def read(ctx):
    return setup_total(ctx, "program.compile.calls", "program.cache_load.calls")
