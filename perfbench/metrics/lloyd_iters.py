def read(ctx):
    iters = [float(f["result"]["n_iter"]) for f in ctx.record["fits"] if "n_iter" in f["result"]]
    return sum(iters) / len(iters) if iters else None
