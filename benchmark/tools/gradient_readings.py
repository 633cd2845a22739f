"""``tools/readings.py`` for a cell whose family also sets the first
gradient tensor against tensor (the family gives ``gradient_readings``:
the run's ``raw`` holds a ``gradient_table``), many seeds in one
process. The family is the driver the cell's mix names:

    python benchmark/tools/gradient_readings.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 11,12] \
        [--controls fp8,half_batch] [--steps 1] [--seconds 0] \
        [--fault <name>] [--out chiprun_out/x.json]

For every seed the cell's own run with the window given (``--seconds
0``: one step), judged through the harness's own ``judge`` under the
committed limits. For the control seeds also the reference in the
precision below put in the program's place, and the reference fed half
of each batch: their first gradient goes through the same table against
the float32 reference's, so every number the cell compares is read for
them too. ``--steps 1`` follows one optimizer step instead of the mix's
three: a third of the reference's time, for readings of the numbers
that the first step alone gives (``grad_gap``, ``loss1`` and the
``*_dir_gap``; ``change_gap`` and ``pairs_gap`` then read another
quantity and are left out of the row). ``--fault`` plants one of the
family's ``FAULTS`` in the PROGRAM for every seed given (the hybrid
family's ``carry`` and ``conv``: the scan, or the conv, told nothing of
the documents), to read what the cell's numbers make of it at its own
size. The rows keep the tables, so a number over other leaves or layers
can be read off them afterwards. On the CPU it only rehearses
(--rehearse, at the cell's own rehearsal widths).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def first_step_only(name: str) -> bool:
    """A number the first optimizer step alone gives."""
    return name in ("grad_gap", "loss1") or name.endswith("_dir_gap")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="fp8,half_batch")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness as hs
    from benchmark.tools.readings import control_readings, judged

    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    family = hs.driver_of(hs.cell_files(bench, hs.find_cell(
        bench, args.workload)))
    if args.fault:
        faults = getattr(family, "FAULTS", {})
        if args.fault not in faults:
            ap.error(f"--fault: {family.__name__} plants "
                     f"{sorted(faults) or 'none'}")
        faults[args.fault]()
    if args.rehearse:
        from benchmark.rehearse import shrink_for
        shrink = shrink_for(args.workload)

    def override(files):
        if args.rehearse:
            shrink(files)
        if args.steps is not None:
            files["mix"]["check"]["steps"] = args.steps

    def verdict(readings, limits):
        if args.steps is not None:
            readings = {k: v for k, v in readings.items()
                        if first_step_only(k)}
        return judged(readings, limits)

    def listed(table):
        return {k: t.tolist() for k, t in table.items()}

    with_control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = hs.make_ctx(args.workload, seed, args.seconds, False,
                          require_chip=not args.rehearse, override=override)
        t0 = time.perf_counter()
        facts = hs.driver_of(ctx).run(ctx)
        raw = facts["raw"]
        row = {"seed": seed, "fault": args.fault,
               "steps": ctx["mix"]["check"]["steps"],
               "program": verdict(facts["readings"], ctx["limits"]),
               "window_steps": facts["work"]["steps"],
               "window_s": facts["window_s"], "setup_s": facts["setup_s"],
               "memory_peak_bytes": facts["device"]["memory_peak_bytes"],
               "run_s": time.perf_counter() - t0}
        tables = {"program": listed(raw["gradient_table"])}
        if seed in with_control:
            t0 = time.perf_counter()
            row["control"] = {}
            for k, (got, table) in control_readings(
                    raw, args.controls.split(",")).items():
                row["control"][k] = verdict(got, ctx["limits"])
                tables[k] = listed(table)
            row["control_s"] = time.perf_counter() - t0
        del facts, raw
        print(json.dumps(row), flush=True)
        rows.append(dict(row, tables=tables))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
