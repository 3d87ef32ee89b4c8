"""perfbench.diag — one run of a ``fit_loop`` cell with every fit stamped.

    python3 -m perfbench.diag --out <file.json> --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is ``perfbench.run``'s own (same set-up, window, comparison and result
line) with two more clock reads a fit: where ``fit`` returns and where the
model's read ends. For a run that reads slow it says where the time went:
``<file.json>`` holds each fit's wall, dispatch (``fit_ms``), read (``read_ms``:
the host waiting for the device) and the gap before it; a traced run adds, fit
by fit from the device's own trace, its busy time, the operations that ran,
the sweeps over the rows (operations over 1 ms) and the longest idle gap. A
slow fit whose busy time is ordinary waited on the host; one whose sweeps are
longer ran slow on the device. Nothing here is part of a benchmark run.
"""

from __future__ import annotations

import json
import sys
import time

from perfbench import run as bench_run
from perfbench import xplane
from perfbench.drivers import fit_loop


def stamped(stamps: list):
    """``fit_loop.one_fit`` with the two more clock reads."""
    def one_fit(ctx, x):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("fit"):
            model = fit_loop.build_estimator(ctx.config).fit(x)
        t_fit = time.perf_counter()
        with jax.profiler.TraceAnnotation("model_read"):
            result = fit_loop.read_model(model, ctx.config)
        t1 = time.perf_counter()
        stamps.append((t0, t_fit, t1))
        return {"t0": t0, "t1": t1, "result": result}

    return one_fit


def host_side(record: dict, stamps: list) -> dict:
    start, end = record["window"]
    fits = [s for s in stamps if s[0] >= start]  # the warm-up fit came before
    rows = [{"gap_ms": 1e3 * (t0 - (fits[i - 1][2] if i else start)), "fit_ms": 1e3 * (t_fit - t0),
             "read_ms": 1e3 * (t1 - t_fit), "wall_ms": 1e3 * (t1 - t0)}
            for i, (t0, t_fit, t1) in enumerate(fits)]
    walls = sorted(r["wall_ms"] for r in rows)
    return {"rows_per_s": record["rows"] * len(fits) / (end - start), "window_s": end - start,
            "wall_ms_min_med_max": [walls[0], walls[len(walls) // 2], walls[-1]],
            "gap_ms_max": max(r["gap_ms"] for r in rows), "fits": rows,
            "counters": {k: v for k, v in record["counters"].items() if v and not k.startswith("fit.stage.")}}


def device_side(reduced: xplane.Reduced) -> list:
    """Per fit (its ``fit`` span's start to its ``model_read`` span's end), from
    the first device's trace."""
    spans = sorted((s, e, n) for n, s, e in reduced.trace.spans if n in ("fit", "model_read"))
    starts = [s for s, _, n in spans if n == "fit"]
    ends = [e for _, e, n in spans if n == "model_read"]
    ordinal = min(reduced.busy)
    ops = xplane.leaves(reduced.trace.devices[ordinal])
    out = []
    for lo, hi in zip(starts, ends):
        mine = [o for o in ops if lo <= o.start < hi]
        sweeps = [o.dur for o in mine if o.dur > 1e6]
        busy = xplane.clip(reduced.busy[ordinal], lo, hi)
        out.append({"span_ms": (hi - lo) / 1e6, "busy_ms": xplane.total(busy) / 1e6, "ops": len(mine),
                    "sweeps": len(sweeps), "sweep_ms_sum": sum(sweeps) / 1e6,
                    "sweep_ms_max": max(sweeps, default=0.0) / 1e6,
                    "idle_gap_ms_max": max((b[0] - a[1] for a, b in zip(busy, busy[1:])), default=0.0) / 1e6})
    return out


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    out = argv.pop(argv.index("--out") + 1)
    argv.remove("--out")
    stamps, seen = [], []
    judge, one_fit = bench_run.judge, fit_loop.one_fit
    fit_loop.one_fit = stamped(stamps)
    bench_run.judge = lambda ctx: (seen.append(ctx), judge(ctx))[1]
    try:
        rc = bench_run.main(argv)
    finally:
        bench_run.judge, fit_loop.one_fit = judge, one_fit
    if not seen:
        return rc  # the run printed why it could not be made
    record = seen[0].record
    doc = dict(host_side(record, stamps), rc=rc)
    if record.get("trace") is not None:
        doc["traced_fits"] = device_side(record["trace"])
    with open(out, "w") as fh:
        json.dump(doc, fh)
    print("diag: %.1f rows/s, a fit's wall ms min / median / max %.1f / %.1f / %.1f, largest gap %.2f ms" % (
        doc["rows_per_s"], *doc["wall_ms_min_med_max"], doc["gap_ms_max"]), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
