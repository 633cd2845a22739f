"""Rehearsal: every cell of BENCHMARK.json end to end at tiny widths on
the CPU (run by hand before chip time is spent):

    JAX_PLATFORMS=cpu python benchmark/rehearse/cells_cpu.py [workload ...]

A cell on four chips needs XLA_FLAGS=--xla_force_host_platform_device_count=4.
It prints each result line; the numbers are rehearsal output and mean
nothing about the chip.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("COMPILE_CACHE", "0")


def main(argv) -> int:
    from benchmark import harness as hs
    from benchmark import run
    from benchmark.rehearse.tiny import shrink
    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    names = argv or [c["name"] for c in bench["workloads"]]
    bad = 0
    for name in names:
        for trace in (False, True):
            out = run.run_cell(name, seed=2**31 + 12345, seconds=3.0,
                               trace=trace, require_chip=False,
                               t_start=time.perf_counter(), override=shrink)
            hs.emit(out["result"], out["checks"], out["notes"])
            bad += not out["result"]["correct"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
