"""Shared by the readers of the program-load counters.

The program's ``jax.monitoring`` listener (``spark_rapids_ml_tpu/utils/
tracing.py``: ``register_program_listener``) adds what jax reports of every
program it traces, lowers, compiles or loads from the persistent cache to
``program.<phase>.ns`` and ``.calls``. A reader runs after the window: the
set-up's share is a counter's total now less its change over the window
(``ctx.record["counters"]``), so it covers the WHOLE set-up (``import`` +
``rows`` + ``warmup`` + ``arm``: the listener is registered when the
program is first imported), the data generator's one program in ``rows``
with it (``fit_loop`` takes no snapshot at its ``warmup`` mark). A program
without the listener (the parent of the PR that brought it) has no such
counter: every reader then returns ``None`` and the metric is left out.
"""

from __future__ import annotations


def setup_total(ctx, *counters: str):
    """The counters' summed total less their change over the window; ``None``
    where one of them does not exist."""
    from spark_rapids_ml_tpu.utils import tracing

    now = tracing.counters("program.")
    if not all(name in now for name in counters):
        return None
    window = ctx.record.get("counters") or {}
    return sum(now[name] - window.get(name, 0) for name in counters)


def setup_s(ctx, *phases: str):
    ns = setup_total(ctx, *(f"program.{phase}.ns" for phase in phases))
    return None if ns is None else ns / 1e9
