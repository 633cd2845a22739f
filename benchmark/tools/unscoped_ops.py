"""The largest device operations that the program's scope table cannot
name (what ``unscoped_device_share.train`` sums), by their instruction:
a share says nothing about which operations they are.

    python benchmark/tools/unscoped_ops.py --workload <cell> --seed <n> \
        [--seconds 51] [--top 12]

One traced run of the cell, as ``run.py --trace 1`` makes it; the last
line is ``{"seconds_in_all", "operations": [[seconds, events,
instruction text, op_name], ...]}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def unscoped(trace: dict, top: int = 12) -> dict:
    from benchmark.readers import program_trace as pt
    prog = pt.program()
    table = {}
    for t in prog.RECORD.scope_tables.values():
        table.update(t)
    rows = []
    for text, seconds in trace["op_time"].items():
        m = pt._INSTRUCTION.match(text)
        op_name = table.get(m.group(1), "") if m else ""
        if prog.scope_path(op_name) is None:
            rows.append([round(seconds, 6), trace["op_count"].get(text, 0),
                         text[:120], op_name[-100:]])
    rows.sort(reverse=True)
    return {"seconds_in_all": round(sum(r[0] for r in rows), 6),
            "operations": rows[:top]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness as hs
    from benchmark import trace as tr
    from benchmark.rehearse.tiny import shrink
    ctx = hs.make_ctx(args.workload, args.seed, args.seconds, True,
                      require_chip=not args.rehearse,
                      override=shrink if args.rehearse else None)
    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    facts = hs.driver_of(ctx).run(ctx)
    trace = tr.reduce_dir(ctx["trace_dir"], facts["trace_window"],
                          not args.rehearse, facts["sizes"])
    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    print(json.dumps(unscoped(trace, args.top)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
