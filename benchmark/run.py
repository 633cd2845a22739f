"""python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the chips of this machine: find
the TPU (no chip: fail, no CPU fallback), build the cell from its files,
warm exactly its shapes, measure for ``--seconds``, compare what the
timed path produced with the plain reference, print the result as the
last line of stdout. The cell, its configuration, its traffic and its
metrics are all found by name: nothing here knows any of them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_start: float = None,
             override=None) -> dict:
    """Everything but the printing. ``override(files)`` lets a rehearsal
    or a test shrink the configuration; the command line never does."""
    from benchmark import harness as hs
    ctx = hs.make_ctx(workload, seed, seconds, trace,
                      require_chip=require_chip, override=override,
                      t_start=T_START if t_start is None else t_start)
    bench, cell, trace_dir = ctx["bench"], ctx["cell"], ctx["trace_dir"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    facts = hs.driver_of(ctx).run(ctx)
    facts["mix"], facts["config"] = ctx["mix"], ctx["config"]
    if trace:
        from benchmark import trace as tr
        facts["trace"] = tr.reduce_dir(trace_dir, facts["trace_window"],
                                       require_chip, facts["sizes"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    checks = hs.judge(facts["readings"], ctx["limits"])
    unjudged = {k: v for k, v in facts["readings"].items()
                if k not in checks}
    if unjudged:
        facts["notes"].append({"note": "read and not compared (no upper "
                               "reading: benchmark/limits)", **unjudged})
    device = dict(facts["device"])
    result = {"correct": hs.is_correct(checks),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]),
              "metrics": hs.read_metrics(
                  hs.wanted_metrics(bench, cell, bool(trace)), facts),
              "device": device}
    if trace:
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]
        result["breakdown"] = facts["trace"]["breakdown"]
    return {"result": result, "checks": checks, "notes": facts["notes"]}


def main(argv=None) -> int:
    args = parse(sys.argv[1:] if argv is None else argv)
    from benchmark import harness as hs
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except hs.BenchFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    hs.emit(out["result"], out["checks"], out["notes"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
