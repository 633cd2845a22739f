"""A scope's share of its matmul roofline, in one phase: the least time
the chip could take for the frozen projections of the micro-passes the
trace shows, over the device time of the operations under the scope in
that phase. One pass multiplies rows x seq positions with every layer's
projection weights (2 FLOP a weight and position, over peak bf16
FLOP/s: at these shapes the operations bound it, not the bytes). The
passes are counted, never assumed: the ``op_count`` of the longest
operation of that phase under ``once_a_pass`` (the vocabulary matmul
runs once a micro-pass whatever the remat policy). Forward only by
default, because every implementation runs the forward exactly once a
micro-step, so the same work is billed whatever implements it."""

import re

from benchmark import flops
from benchmark.readers import program_trace as pt


def read(facts, scope, phase="forward", once_a_pass="^unembed"):
    ops = pt.attributed(facts)
    work = facts.get("work") or {}
    if not ops or "rows_per_call" not in work:
        return None
    in_phase = [o for o in ops if pt.phase_of(o[2]) == phase]
    counted = [o for o in in_phase if o[3] and re.search(once_a_pass, o[3])]
    spent = sum(o[0] for o in in_phase if o[3] and re.search(scope, o[3]))
    if not counted or not spent:
        return None
    devices = max(int(facts["trace"].get("devices", 1)), 1)
    passes = max(counted, key=lambda o: o[0])[1] / devices
    dims = facts["dims"]
    per_pass = (2.0 * work["rows_per_call"] * work["seq"] * dims["layers"]
                * flops.layer_matmul_params(dims))
    least = passes * per_pass / facts["peaks"]["flops_bf16"]
    facts.setdefault("notes", []).append({
        "note": f"roofline of scope {scope} ({phase})", "passes": passes,
        "flop_a_pass": per_pass, "least_s": least, "spent_s": spent})
    return 100.0 * least / spent
