def read(ctx):
    trace = ctx.record.get("trace")
    if trace is None:
        return None
    return 1e3 * trace.device_time() / len(trace.fit_spans())
