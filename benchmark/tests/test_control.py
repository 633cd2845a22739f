"""The control of each cell, at a size a test run can hold: the plain
reference computed in the precision below the configuration's, put in
the program's place, has to come out NOT correct under the cell's own
committed limits, through the harness's own ``judge``, by every number
the cell compares (``drivers/train.py::compared``: the first gradient
tensor against tensor too, where the family reads it). (On the chip, at
the cell's own size, benchmark/tools/readings.py and
gradient_readings.py judge the same way; PERF.md has those readings.)

The tiny model runs in bfloat16 as the cells do, so the program's own
readings here are near the size they have on the chip, and the test also
shows them inside the limits. Every cell is shrunk by ``rehearse/
tiny.py``, which keeps a routed or state-space layer's own widths: at a
family's own rehearsal widths (``rehearse/*_tiny.py``) bfloat16 reads
the routed cell's program over its ``grad_gap`` limit (PERF.md).
"""

import pytest

from benchmark import harness as hs
from benchmark.rehearse.tiny import shrink
from benchmark.tools import readings


@pytest.mark.parametrize("workload", [
    c["name"] for c in hs.load_json(hs.ROOT, "BENCHMARK.json")["workloads"]])
def test_control_is_not_correct(workload):
    ctx = hs.make_ctx(workload, 2 ** 31 + 99, 1.0, False, require_chip=False,
                      override=lambda files: shrink(files, "bfloat16"))
    facts = hs.driver_of(ctx).run(ctx)
    limits = ctx["limits"]
    program = readings.judged(facts["readings"], limits)
    assert program["correct"], program
    control, _ = readings.control_readings(
        facts["raw"], [limits["control"]])[limits["control"]]
    assert set(control) == set(facts["readings"])
    verdict = readings.judged(control, limits)
    assert not verdict["correct"] and verdict["failed"], verdict
