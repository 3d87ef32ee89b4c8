from perfbench.metrics._common import median


def read(ctx):
    trace = ctx.record.get("trace")
    if trace is None:
        return None
    return median(t / 1e6 for t in trace.host_wait_per_fit())
