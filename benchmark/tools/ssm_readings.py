"""``tools/gradient_readings.py`` for the hybrid state-space / attention
cell (``drivers/train_ssm.py``: the run's ``raw`` holds a
``gradient_table``, and the numbers over it are ``grad_dir_gap`` and
``ssm_dir_gap``), many seeds in one process:

    python benchmark/tools/ssm_readings.py --seeds 11,12,... \
        [--control-seeds 11,12] [--controls fp8,half_batch] \
        [--seconds 0] [--out chiprun_out/x.json]

For every seed the cell's own run with the window given (``--seconds
0``: one step), judged through the harness's own ``judge`` under the
committed limits. For the control seeds also the reference in the
precision below put in the program's place, and the reference fed half
of each batch: their first gradient goes through the same table against
the float32 reference's, so every number the cell compares is read for
them too. ``--fault carry|conv`` plants a fault of the mechanism in the
PROGRAM for every seed given (the scan, or the conv, told nothing of
the documents), to read what the cell's numbers make of it at its own
size. On the CPU it only rehearses (--rehearse).
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

CELL = "granite4hsmall_ep4_l20.qlora_sft_packed_8k_ssm"


def control_readings(raw: dict, which) -> dict:
    """{control: (the numbers `correct` compares, its table)}, with the
    control in the program's place and the float32 reference unchanged."""
    from benchmark import check
    from benchmark.drivers import train, train_ssm
    args = raw["reference_args"]
    n = len(args[-1][0]["inputs"])
    out = {}
    for name in which:
        kw = ({"keep_rows": slice(0, n // 2)} if name == "half_batch"
              else {"mode": name})
        low = train.reference_readings(*args, **kw)
        pairs = low["dims"].pop("held_pairs")
        table = train_ssm.gradient_table(low["dims"].pop("first_gradient"),
                                         raw["reference"]["gradient"])
        got = check.train_readings(low, raw["reference"])
        got.update(train_ssm.gradient_readings(table))
        got["pairs_gap"] = train_ssm.pairs_gap(pairs, raw["reference_pairs"])
        out[name] = got, table
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="fp8,half_batch")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--fault", choices=("carry", "conv"), default=None)
    args = ap.parse_args(argv)
    if args.fault:
        from gke_ray_train_tpu.ops import ssm
        scan, conv = ssm.ssd_scan, ssm.causal_conv
        if args.fault == "carry":
            ssm.ssd_scan = lambda x, dt, a, b, c, d, seg, **kw: scan(
                x, dt, a, b, c, d, None, **kw)
        else:
            ssm.causal_conv = lambda x, w, b, seg: conv(x, w, b, None)
    from benchmark import harness as hs
    from benchmark.rehearse.granite_tiny import shrink
    from benchmark.tools.readings import judged

    with_control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = hs.make_ctx(args.workload, seed, args.seconds, False,
                          require_chip=not args.rehearse,
                          override=shrink if args.rehearse else None)
        t0 = time.perf_counter()
        facts = hs.driver_of(ctx).run(ctx)
        raw = facts["raw"]
        row = {"seed": seed, "fault": args.fault,
               "program": judged(facts["readings"], ctx["limits"]),
               "steps": facts["work"]["steps"],
               "window_s": facts["window_s"], "setup_s": facts["setup_s"],
               "memory_peak_bytes": facts["device"]["memory_peak_bytes"],
               "run_s": time.perf_counter() - t0}
        tables = {"program": {k: t.tolist() for k, t in
                              raw["gradient_table"].items()}}
        if seed in with_control:
            t0 = time.perf_counter()
            row["control"] = {}
            for k, (got, table) in control_readings(
                    raw, args.controls.split(",")).items():
                row["control"][k] = judged(got, ctx["limits"])
                tables[k] = {n: t.tolist() for n, t in table.items()}
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
