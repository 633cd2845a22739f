"""The routed experts' share of their matmul roofline, forward only:
the least time the chip could take for the three products of the held
pairs of the micro-passes the trace shows (2 FLOP a pair and expert
weight over peak bf16 FLOP/s), over the device time of the forward
operations under the scope (the dequantisation of the bank, the grouped
kernel, the activation). Pairs are the program's own count a step,
averaged over the window's steps and divided over the micro-passes of a
step; the passes are counted from the trace as ``scope_roofline`` counts
them. The kernel itself is ``benchmark/kernels/gmm.json``."""

import re

from benchmark import flops_moe
from benchmark.readers import program_trace as pt


def read(facts, scope="(^|/)moe/experts(/|$)", phase="forward",
         once_a_pass="^unembed(/|$)"):
    ops = pt.attributed(facts)
    work = facts.get("work") or {}
    if not ops or not work.get("step_pairs"):
        return None
    in_phase = [o for o in ops if pt.phase_of(o[2]) == phase]
    counted = [o for o in in_phase if o[3] and re.search(once_a_pass, o[3])]
    spent = sum(o[0] for o in in_phase if o[3] and re.search(scope, o[3]))
    if not counted or not spent:
        return None
    devices = max(int(facts["trace"].get("devices", 1)), 1)
    passes = max(counted, key=lambda o: o[0])[1] / devices
    pairs_a_pass = (sum(work["step_pairs"]) / len(work["step_pairs"])
                    / work["micro_steps"])
    flop = 2.0 * flops_moe.expert_params(facts["dims"]) * pairs_a_pass
    least = passes * flop / facts["peaks"]["flops_bf16"]
    facts.setdefault("notes", []).append({
        "note": f"roofline of scope {scope} ({phase})", "passes": passes,
        "pairs_a_pass": pairs_a_pass, "least_s": least, "spent_s": spent})
    return 100.0 * least / spent
