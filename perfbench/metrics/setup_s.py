from perfbench.metrics._setup import setup_parts


def read(ctx):
    mine = [s for name, s in setup_parts(ctx).items() if name != "start"]
    return sum(mine) if mine else None
