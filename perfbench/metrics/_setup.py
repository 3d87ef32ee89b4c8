"""Shared by the readers of the set-up's parts.

``Context.mark`` (``perfbench/run.py``) ends a part of the set-up at each of
its calls and keeps the seconds under ``ctx.record["setup_parts"]``: ``start``
and ``import`` are the harness's own marks, the rest the driver's
(``fit_loop``: ``rows``, ``warmup``, ``arm``). A record without the parts, or
without the one asked for, gives ``None`` and the metric is left out.
"""

from __future__ import annotations


def setup_parts(ctx) -> dict:
    return ctx.record.get("setup_parts") or {}


def part(ctx, name: str):
    return setup_parts(ctx).get(name)
