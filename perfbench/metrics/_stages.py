"""Shared by the readers of the program's stage counters.

A stage span of the host fit path (``spark_rapids_ml_tpu/utils/tracing.py``:
``StageRange``) adds its duration to ``fit.stage.<stage>.ns`` and 1 to
``.calls``; the site that knows the size adds ``.bytes``. The driver hands
every reader the change of the program's counters over the measured window
(``ctx.record["counters"]``); over the window's fits that is a MEAN per fit,
since a counter has no median. A program without the stages (the parent of
the PR that brought them) has no such counter: every reader then returns
``None`` and the metric is left out.
"""

from __future__ import annotations

TIMED = ("densify", "convert", "place", "solve")  # what host_other_ms takes off a fit's wall


def per_fit(ctx, counter: str):
    """The counter's change over the window, over its fits; ``None`` where
    the counter is absent or did not move."""
    fits = ctx.record.get("fits") or []
    moved = (ctx.record.get("counters") or {}).get(counter)
    if not moved or not fits:
        return None
    return moved / len(fits)


def stage_ms(ctx, stage: str):
    ns = per_fit(ctx, f"fit.stage.{stage}.ns")
    return None if ns is None else ns / 1e6
