"""``tools/readings.py`` for a cell whose driver also sets the first
gradient tensor against tensor (``drivers/train_mla.py``: the run's
``raw`` holds a ``gradient_table``), many seeds in one process:

    python benchmark/tools/gradient_readings.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 11,12] \
        [--controls fp8,half_batch] [--steps 1] [--out chiprun_out/x.json]

For every seed the cell's own run with the shortest window there is
(``--seconds 0``: one step), judged through the harness's own ``judge``
under the committed limits. For the control seeds also the reference in
the precision below put in the program's place, and the reference fed
half of each batch: their first gradient goes through the same table
against the float32 reference's, so every number the cell compares is
read for them too. ``--steps 1`` follows one optimizer step instead of
the mix's three: a third of the reference's time, for readings of the
numbers that the first step alone gives (``grad_gap``,
``grad_dir_gap``, ``attn_dir_gap``; ``change_gap`` and ``pairs_gap``
then read another quantity and are left out of the row). The rows keep the table, so a
number over other leaves or layers can be read off them afterwards.
On the CPU it only rehearses (--rehearse).
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

FIRST_STEP_ONLY = ("grad_gap", "grad_dir_gap", "attn_dir_gap", "loss1")


def control_readings(raw: dict, which) -> dict:
    """{control: (the numbers `correct` compares, its table)}, with the
    control in the program's place and the float32 reference unchanged."""
    from benchmark import check
    from benchmark.drivers import train, train_mla
    args = raw["reference_args"]
    n = len(args[-1][0]["inputs"])
    out = {}
    for name in which:
        kw = ({"keep_rows": slice(0, n // 2)} if name == "half_batch"
              else {"mode": name})
        low = train.reference_readings(*args, **kw)
        pairs = low["dims"].pop("held_pairs")
        table = train_mla.gradient_table(low["dims"].pop("first_gradient"),
                                         raw["reference"]["gradient"])
        got = check.train_readings(low, raw["reference"])
        got.update(train_mla.gradient_readings(table))
        got["pairs_gap"] = train_mla.pairs_gap(pairs, raw["reference_pairs"])
        out[name] = got, table
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="fp8,half_batch")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness as hs
    from benchmark.rehearse.glm_tiny import shrink
    from benchmark.tools.readings import judged

    def override(files):
        if args.rehearse:
            shrink(files)
        if args.steps is not None:
            files["mix"]["check"]["steps"] = args.steps

    def verdict(readings, limits):
        if args.steps is not None:
            readings = {k: v for k, v in readings.items()
                        if k in FIRST_STEP_ONLY}
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
        row = {"seed": seed, "steps": ctx["mix"]["check"]["steps"],
               "program": verdict(facts["readings"], ctx["limits"]),
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
