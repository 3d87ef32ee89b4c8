import re

from perfbench.metrics._common import work


def read(ctx):
    trace = ctx.record.get("trace")
    if trace is None:
        return None
    # an operation reads the rows if its HLO text names a two-dimensional
    # array as wide as the configuration's rows
    rows_operand = re.compile(rf"\[\d+,{ctx.cols}\]")
    sweep_s = trace.device_time(lambda op: bool(rows_operand.search(op.name)))
    if not sweep_s:
        return None  # no operation found that reads the rows: nothing to read
    least = work(ctx)["fit_bytes"] / (ctx.chips * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(trace.fit_spans()) / sweep_s
