import re

from perfbench.metrics._common import work

WHILE = re.compile(r"^%?while[.\d]* = \(")


def read(ctx):
    trace = ctx.record.get("trace")
    if trace is None:
        return None
    # the epoch loop is the `while` whose carry (the tuple it returns) holds
    # the float32 layout, n rows by nComponents: its duration is the epochs'
    layout = f"f32[{ctx.rows},{int(ctx.config['n_components'])}]"

    def is_loop(op):
        head, _, rest = op.name.partition(" while(")
        return bool(rest) and WHILE.match(head) and layout in head

    start, end = trace.window
    loop_ns = max(
        sum(op.dur for op in ops if start <= op.start < end and is_loop(op))
        for ops in trace.trace.devices.values()
    )
    if not loop_ns:
        return None  # no epoch loop in the trace: nothing to read
    least = work(ctx)["layout_bytes"] / (ctx.chips * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(trace.fit_spans()) / (loop_ns / 1e9)
