"""perfbench.control — the readings a limit is set from, on the chip.

    python3 -m perfbench.control --workload <cell> --seeds 1,2,3 [--faults]

For each seed, in one process: the cell's rows from the seed, ONE public fit
as the window makes it (the lower reading), the plain reference, and every
control of the configuration's reference put in the sound fit's place (the
upper readings). ``--faults`` adds the planted faults of
``perfbench/tests/test_faults.py`` at the cell's own size. Each result goes
through the same limits check as a run's (``perfbench.run.check``): a JSON
line per seed with, for each, the numbers compared and ``correct``. Exits 1
unless the program is correct and every listed control is not, on every
seed. Nothing here is part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys

from perfbench import run as bench_run
from perfbench.drivers import fit_loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench, cell, workload, config = bench_run.load_cell(args.workload, args.rehearse)
    device, peaks = bench_run.find_device(int(cell["chips"]), args.rehearse)
    from spark_rapids_ml_tpu.core.serving import configure_compile_cache

    configure_compile_cache()
    reference = bench_run.module_for("reference", cell["config"])
    limits = config["limits"]
    as_hoped = True
    for seed in (int(s) for s in args.seeds.split(",")):
        args.seed = seed
        ctx = bench_run.Context(args, bench, cell, workload, config, peaks, device)
        x = fit_loop.make_rows(ctx)
        ref = reference.reference(x, config)

        def judged(result):
            numbers = reference.compare(result, ref)
            return {"numbers": numbers, "correct": not bench_run.check(numbers, limits)}

        line = {"seed": seed, "cell": cell["name"], "device": device, "limits": limits,
                "program": judged(fit_loop.one_fit(ctx, x)["result"]),
                "controls": {name: judged(control(ctx, x))
                             for name, control in reference.controls().items()}}
        if args.faults:
            line["faults"] = {name: judged(fault(ctx, x))
                              for name, fault in reference.faults().items()}
        # a control that only the chip's arithmetic shows is read in a
        # rehearsal and not held to fail there
        listed = [line["controls"][name]["correct"] for name in config["controls"]
                  if not (args.rehearse and name in config.get("controls_chip_only", []))]
        as_hoped = as_hoped and line["program"]["correct"] and not any(listed)
        print(json.dumps(line), flush=True)
        if hasattr(x, "delete"):
            x.delete()
        del x, ref
    return 0 if as_hoped else 1


if __name__ == "__main__":
    sys.exit(main())
