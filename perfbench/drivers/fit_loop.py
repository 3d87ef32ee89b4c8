"""fit_loop — the window is public ``Estimator.fit`` calls, back to back.

Set-up: the cell's rows are made on the device from the seed (copied out once
to a list of numpy partitions for a host cell), then ONE fit warms the cell's
own shapes; each ends a part of the set-up (``ctx.mark``: ``rows``, ``warmup``,
and ``arm`` at the window's start). The window starts
after that, runs fits back to back, each ended by reading the model's public
result on the host, and ends when the fit in flight at ``--seconds``
completes. Every fit's result is kept for the comparison.
"""

from __future__ import annotations

import glob
import os
import shutil
import time

from perfbench import data, xplane


# jax reports this once for every program it lowers, compiled or loaded from
# the persistent cache: inside the window there should be none
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def build_estimator(config: dict):
    spec = config["estimator"]
    est = data.resolve(spec["class"])()
    for name, value in spec["set"].items():
        getattr(est, "set" + name)(value)
    return est


def read_model(model, config: dict) -> dict:
    """The model's public result on the host: ends a fit."""
    import numpy as np

    out = {}
    for key, attr in config["estimator"]["result"].items():
        value = getattr(model, attr)
        out[key] = np.asarray(value() if callable(value) else value)
    return out


# rows copied out of the device at a time: pieces of 120 MB came out at a
# third of the rate of pieces of 12 MB (15 s against 4 s for 4.8 GB)
COPY_OUT_ROWS = 1000


def make_rows(ctx):
    """The rows handed to fit: one device array, or a list of host partitions."""
    import numpy as np

    gen = ctx.config["data"]
    where = ctx.workload["rows_live"]
    if where not in ("device", "host_partitions"):
        raise ValueError(f"rows_live must be device or host_partitions, got {where!r}")
    x = data.generate(gen["generator"], ctx.args.seed, ctx.rows, ctx.cols, gen["params"])
    if where == "device":
        return x
    # as Spark hands an executor its rows: a list of C-contiguous blocks
    step = int(ctx.workload["partition_rows"])
    parts = []
    for lo in range(0, ctx.rows, step):
        part = np.empty((min(step, ctx.rows - lo), ctx.cols), dtype=np.float32)
        for at in range(0, part.shape[0], COPY_OUT_ROWS):
            to = min(at + COPY_OUT_ROWS, part.shape[0])
            part[at:to] = np.asarray(x[lo + at : lo + to])
        parts.append(part)
    x.delete()
    return parts


def one_fit(ctx, x) -> dict:
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("fit"):
        model = build_estimator(ctx.config).fit(x)
    with jax.profiler.TraceAnnotation("model_read"):
        result = read_model(model, ctx.config)
    return {"t0": t0, "t1": time.perf_counter(), "result": result}


def run(ctx) -> None:
    import jax

    from spark_rapids_ml_tpu.utils import tracing

    x = make_rows(ctx)
    ctx.mark("rows")
    # warm-up: compiles or loads every program of the cell. A list of
    # partitions is fitted one partition at a time, so two of them (two, for the
    # sum of partials) hold every shape the whole list does.
    one_fit(ctx, x[:2] if isinstance(x, list) else x)
    ctx.mark("warmup")
    trace_dir = os.path.join(ctx.scratch, "trace", ctx.cell["name"])
    if ctx.args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False  # the reduction reads operations, not programs
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counters0 = tracing.counters()
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **__: lowered.append(name) if name == LOWERING_EVENT else None
    )
    fits = []
    start = ctx.mark("arm")
    try:
        while True:
            fits.append(one_fit(ctx, x))
            if fits[-1]["t1"] - start >= ctx.args.seconds:
                break
        end = fits[-1]["t1"]
        compiles = len(lowered)
    finally:
        if ctx.args.trace:
            jax.profiler.stop_trace()
    ctx.record.update(
        data=x, fits=fits, window=(start, end), rows=ctx.rows,
        compiles_in_window=compiles,
        counters={k: v - counters0.get(k, 0) for k, v in tracing.counters().items()},
    )
    if ctx.args.trace:
        files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file under {trace_dir}, found {files}")
        trace = xplane.load(files[0])
        shutil.rmtree(trace_dir, ignore_errors=True)
        if ctx.rehearse and not trace.devices:
            return  # a CPU has no device plane: the reduction has its own tests
        reduced = xplane.reduce(trace, chips=ctx.chips)
        ctx.record["trace"] = reduced
        ctx.record["trace_device"] = {
            "busy_s": reduced.busy_s_mean, "window_s": reduced.window_s,
        }
        ctx.record["breakdown"] = reduced.breakdown()
