from perfbench import xplane
from perfbench.metrics._common import work


def read(ctx):
    trace = ctx.record.get("trace")
    if trace is None:
        return None
    gemm_s = trace.device_time(xplane.is_matmul) / len(trace.fit_spans())
    if not gemm_s:
        return None  # no matrix multiplication found: nothing to read, never 0
    need = work(ctx)
    least = max(need["gemm_flops"] / (ctx.chips * ctx.peaks["bf16_flops_per_s"]),
                need["gemm_bytes"] / (ctx.chips * ctx.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / gemm_s
