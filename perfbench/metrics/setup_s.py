def read(ctx):
    return ctx.record["setup_s"]
