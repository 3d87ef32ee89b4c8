import re

from perfbench import xplane

COLLECTIVE = re.compile(
    r"^%?[\w.-]* = .*?\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)


def read(ctx):
    trace = ctx.record.get("trace")
    if trace is None:
        return None
    start, end = trace.window
    worst, seen = 0.0, False
    for ops in trace.trace.devices.values():
        leaves = xplane.leaves(ops)
        comm = [(o.start, o.end) for o in leaves if COLLECTIVE.search(o.name)]
        seen = seen or bool(comm)
        rest = [(o.start, o.end) for o in leaves if not COLLECTIVE.search(o.name)]
        exposed = xplane.subtract(xplane.clip(xplane.union(comm), start, end),
                                  xplane.union(rest))
        worst = max(worst, xplane.total(exposed))
    if not seen:
        return None  # no collective in the trace: nothing to read
    return worst / 1e6 / len(trace.fit_spans())
