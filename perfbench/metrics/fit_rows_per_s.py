def read(ctx):
    start, end = ctx.record["window"]
    return ctx.record["rows"] * len(ctx.record["fits"]) / (end - start)
