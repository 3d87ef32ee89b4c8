import re

from perfbench.metrics._common import work


def read(ctx):
    trace = ctx.record.get("trace")
    if trace is None or not (ctx.record.get("counters") or {}).get("forest.grow.tree_levels"):
        return None  # no traced run, or a program without the level builder: nothing to read
    # the binning pass (quantile sort, comparison with the edges) is what reads
    # the float32 rows: its operations name a two-dimensional float32 array as
    # wide as the rows; every other operation of a fit is the growth's
    rows_operand = re.compile(rf"f32\[\d+,{ctx.cols}\]")
    grow_s = trace.device_time(lambda op: not rows_operand.search(op.name))
    if not grow_s:
        return None
    least = work(ctx)["fit_bytes"] / (ctx.chips * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(trace.fit_spans()) / grow_s
