"""The readings that the limits of `correct` are set from, many seeds in
one process (set-up is long, the compiled programs are shared):

    python benchmark/tools/readings.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 11,12,13] [--controls fp8,half_batch] \
        [--seconds 5] [--out chiprun_out/x.json]

For every seed: the cell's own run (short window), whose compared numbers
are the *lower* readings. For the control seeds also the *upper* ones:
the reference in int8 or fp8 put in the program's place, and the
reference fed half of each batch. Every set of readings goes through the
harness's own ``judge`` under the cell's committed limits, and the line
says how it came out: the program correct, each control not. Run on the
chip at the cell's own size; on the CPU it only rehearses (--rehearse).
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


# the precisions next below the bfloat16 the configurations compute in
# (matmul operands rounded to int8 or fp8), and the fault a reference
# can stand in for
CONTROLS = ("int8", "fp8", "half_batch")


def control_readings(raw: dict, which=CONTROLS) -> dict:
    """{control: (the numbers `correct` compares, the first gradient's
    table or None)}, with the control in the program's place and the
    float32 reference unchanged: every number the cell compares, as the
    run reads it (``drivers/train.py::compared``)."""
    from benchmark.drivers import train
    args = raw["reference_args"]
    n = len(args[-1][0]["inputs"])
    out = {}
    for name in which:
        kw = ({"keep_rows": slice(0, n // 2)} if name == "half_batch"
              else {"mode": name})
        low = train.reference_readings(*args, **kw)
        out[name] = train.compared(low, raw["reference"], raw["family"])
    return out


def judged(readings: dict, limits: dict) -> dict:
    from benchmark import harness as hs
    checks = hs.judge(readings, limits)
    return {"readings": readings, "correct": hs.is_correct(checks),
            "failed": sorted(k for k, (v, lim) in checks.items()
                             if not (v == v and v <= lim))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness as hs
    from benchmark.rehearse.tiny import shrink
    with_control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = hs.make_ctx(args.workload, seed, args.seconds, False,
                          require_chip=not args.rehearse,
                          override=shrink if args.rehearse else None)
        t0 = time.perf_counter()
        facts = hs.driver_of(ctx).run(ctx)
        row = {"seed": seed, "program": judged(facts["readings"],
                                               ctx["limits"]),
               "run_s": time.perf_counter() - t0}
        if seed in with_control:
            t0 = time.perf_counter()
            row["control"] = {
                k: judged(v, ctx["limits"]) for k, (v, _) in
                control_readings(facts["raw"],
                                 args.controls.split(",")).items()}
            row["control_s"] = time.perf_counter() - t0
        del facts
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
