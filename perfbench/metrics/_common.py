"""Shared by the metric readers: the median, and the cell's required work."""

from __future__ import annotations

import statistics


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def work(ctx) -> dict:
    from perfbench.run import module_for

    results = [fit["result"] for fit in ctx.record["fits"]]
    return module_for("work", ctx.cell["config"]).work(
        ctx.record["rows"], ctx.cols, ctx.config, results
    )
