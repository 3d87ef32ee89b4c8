def read(ctx):
    trace = ctx.record.get("trace")
    if trace is None:
        return None
    return 100.0 * trace.idle_share_worst()
