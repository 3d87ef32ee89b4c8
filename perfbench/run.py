"""perfbench.run — one process, one cell, once.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one cell, one driver or one
metric is a file found by the name ``BENCHMARK.json`` gives it (see
``perfbench/README.md``); this module holds no list of its own. The LAST
line of stdout is the result object; a run that cannot measure (no listed
chip, too few chips, a missing file) prints no result and exits non-zero.
"""

from __future__ import annotations

import time

# "process start": before any heavy import. The set-up part ``start`` runs from
# here to the return of find_device and is NOT in setup_s (metrics/setup_s.json)
_T0 = time.perf_counter()

import argparse
import importlib
import json
import os
import sys
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(RuntimeError):
    """The run cannot be made as asked: no result line, exit code 2."""


def load_json(*parts: str) -> dict:
    path = os.path.join(ROOT, *parts)
    if not os.path.isfile(path):
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as fh:
        return json.load(fh)


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, group by group: how a rehearsal's
    tiny sizes (the ``rehearse`` group of a file) replace the real ones."""
    out = dict(base)
    for key, value in over.items():
        both = isinstance(value, dict) and isinstance(out.get(key), dict)
        out[key] = merged(out[key], value) if both else value
    return out


def load_cell(workload_name: str, rehearse: bool) -> tuple:
    """(BENCHMARK.json, its entry of the cell, the cell's file, its
    configuration's file), the two files at the rehearsal's sizes if asked."""
    bench = load_json("BENCHMARK.json")
    cell = named(bench["workloads"], workload_name, "workload")
    workload = load_json("perfbench", "workloads", cell["name"] + ".json")
    config = load_json(named(bench["configs"], cell["config"], "config")["file"])
    if rehearse:
        workload = merged(workload, workload["rehearse"])
        config = merged(config, config["rehearse"])
    return bench, cell, workload, config


def named(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchError(f"BENCHMARK.json has no {what} named {name!r}")


@dataclass
class Context:
    """What a driver, a metric reader and a reference get to see."""

    args: argparse.Namespace
    bench: dict       # BENCHMARK.json
    cell: dict        # its workloads[] entry
    workload: dict    # perfbench/workloads/<cell>.json
    config: dict      # the configuration's file
    peaks: dict       # this device's row of peaks.json ({} in a rehearsal)
    device: dict      # platform / kind / count as jax reports them
    t0: float = _T0
    scratch: str = os.path.join(ROOT, "perfbench_out")
    record: dict = field(default_factory=dict)  # filled by the driver

    def mark(self, part: str, at: float | None = None) -> float:
        """End the set-up part ``part`` now (or at ``at``). A part begins where
        the one before it ended, the first at ``t0``: the parts add up to the
        time from ``t0`` to the last mark. Returns the mark's time."""
        at = time.perf_counter() if at is None else at
        parts = self.record.setdefault("setup_parts", {})
        parts[part] = at - self.t0 - sum(parts.values())
        return at

    @property
    def rehearse(self) -> bool:
        return bool(self.args.rehearse)

    @property
    def rows(self) -> int:
        return int(self.workload["rows"])

    @property
    def cols(self) -> int:
        return int(self.config["num_cols"])

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])


def module_for(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` — a missing file is an error."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"missing file perfbench/{kind}/{name}.py")
    return importlib.import_module(f"perfbench.{kind}.{name}")


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` that this cell reports. An end-to-end metric:
    one without a ``workloads`` key, or one that lists the cell. A per-layer
    metric besides moves an end-to-end metric that the cell reports."""
    def listed(metric):
        return cell in metric.get("workloads", [cell])

    end_to_end = [m for m in bench["end_to_end"] if listed(m)]
    if section == "end_to_end":
        return end_to_end
    moved = {m["name"] for m in end_to_end}
    return [m for m in bench["per_layer"] if listed(m) and m["moves"] in moved]


def find_device(chips: int, rehearse: bool) -> tuple:
    t_python = time.perf_counter()
    import jax

    t_jax = time.perf_counter()
    devices = jax.devices()
    print(f"process start: to find_device {t_python - _T0:.3f} s, import jax {t_jax - t_python:.3f} s, "
          f"jax.devices() {time.perf_counter() - t_jax:.3f} s", file=sys.stderr)
    first = devices[0]
    device = {"platform": first.platform, "kind": first.device_kind, "count": len(devices)}
    peaks = load_json("perfbench", "peaks.json").get(first.device_kind)
    if peaks is None and not rehearse:
        raise BenchError(f"no published peaks for {device}: not a listed chip, no metric")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, jax found {device}")
    return device, peaks or {}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks))


def read_metrics(ctx: Context, section: str) -> dict:
    out = {}
    for entry in cell_metrics(ctx.bench, ctx.cell["name"], section):
        spec = load_json("perfbench", "metrics", entry["name"] + ".json")
        for key in ("unit", "better", "source"):
            if spec[key] != entry[key]:
                raise BenchError(f"metric {entry['name']}: {key} differs from BENCHMARK.json")
        value = module_for("metrics", entry["name"]).read(ctx)
        if value is not None:  # a reader that found nothing reports nothing
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def check(numbers: dict, limits: dict) -> list:
    """The names of the compared numbers that are over their limit (a NaN is)."""
    return [name for name, limit in limits.items() if not float(numbers[name]) <= limit]


def judge(ctx: Context) -> tuple:
    """Compare every fit the window completed with the plain reference.
    Returns (correct, failed_fits, compared) where ``compared`` maps each
    number to its worst value over the fits and its limit."""
    reference = module_for("reference", ctx.cell["config"])
    limits = ctx.config["limits"]
    ref = reference.reference(ctx.record["data"], ctx.config)
    worst = {name: 0.0 for name in limits}
    failed = 0
    for fit in ctx.record["fits"]:
        numbers = reference.compare(fit["result"], ref)
        for name in limits:
            if not float(numbers[name]) <= worst[name]:  # NaN sticks
                worst[name] = float(numbers[name])
        failed += bool(check(numbers, limits))
    compared = {n: {"value": worst[n], "limit": limits[n]} for n in limits}
    return failed == 0 and bool(ctx.record["fits"]), failed, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal of the control flow at tiny sizes: never a "
                         "pass (correct false, no metric, exit 1)")
    args = ap.parse_args(argv)
    try:
        bench, cell, workload, config = load_cell(args.workload, args.rehearse)
        if args.rehearse and cell["chips"] > 1:
            os.environ.setdefault(
                "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell['chips']}"
            )
        device, peaks = find_device(int(cell["chips"]), args.rehearse)
        t_device = time.perf_counter()
        ctx = Context(args, bench, cell, workload, config, peaks, device)
        ctx.mark("start", at=t_device)
        driver = module_for("drivers", workload["driver"])
        # the program's own placement of jax's persistent cache: the env
        # variable where set, else .jax_compile_cache/ in this checkout
        from spark_rapids_ml_tpu.core.serving import configure_compile_cache

        configure_compile_cache()
        ctx.mark("import")
        driver.run(ctx)
        print("set-up: " + ", ".join(f"{k} {v:.3f} s" for k, v in ctx.record["setup_parts"].items()),
              file=sys.stderr)
        device["memory_peak_bytes"] = memory_peak_bytes()
        section = "per_layer" if args.trace else "end_to_end"
        metrics = {} if args.rehearse else read_metrics(ctx, section)
        if args.trace and not args.rehearse:
            device.update(ctx.record["trace_device"])
        breakdown = ctx.record.get("breakdown")
        t_ref = time.perf_counter()
        correct, failed, compared = judge(ctx)
        print(f"reference and comparison {time.perf_counter() - t_ref:.2f} s", file=sys.stderr)
    except BenchError as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2
    result = {
        "correct": correct and not args.rehearse,
        "attempted": len(ctx.record["fits"]),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown and not args.rehearse:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = {"comparison_passed": correct}
    result["setup_parts"] = ctx.record["setup_parts"]
    result["compiles_in_window"] = ctx.record["compiles_in_window"]
    if result["compiles_in_window"]:
        print(f"perfbench: {result['compiles_in_window']} programs were lowered inside the "
              "measured window: the warm-up missed a shape", file=sys.stderr)
    result["compared"] = compared
    for name, pair in compared.items():
        print(f"compared {name} = {pair['value']:.6e}  limit {pair['limit']:.1e}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 1 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
