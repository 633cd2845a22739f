"""``nf4_roofline_mla`` for the hybrid state-space / attention routed
decoder: the frozen projections' share of their matmul roofline, forward
only. One micro-pass multiplies rows x seq positions with every weight
that ``_proj`` holds as an NF4 ``base`` leaf: a state-space layer's two
projections, an attention layer's four, every layer's shared expert
(``flops_ssm.base_params``; the routed experts run under ``moe/experts``
and have ``moe_experts_roofline.train``). 2 FLOP a weight and position
over peak bf16 FLOP/s, over the device time of the forward operations
under the ``base`` scopes; the passes are counted from the trace as
``scope_roofline`` counts them."""

import re

from benchmark import flops_ssm
from benchmark.readers import program_trace as pt


def read(facts, scope="(^|/)base$", phase="forward",
         once_a_pass="^unembed(/|$)"):
    ops = pt.attributed(facts)
    work = facts.get("work") or {}
    if not ops or not work.get("layer_kinds") \
            or "ssm_heads" not in facts["dims"]:
        return None
    in_phase = [o for o in ops if pt.phase_of(o[2]) == phase]
    counted = [o for o in in_phase if o[3] and re.search(once_a_pass, o[3])]
    spent = sum(o[0] for o in in_phase if o[3] and re.search(scope, o[3]))
    if not counted or not spent:
        return None
    devices = max(int(facts["trace"].get("devices", 1)), 1)
    passes = max(counted, key=lambda o: o[0])[1] / devices
    per_pass = (2.0 * work["rows_per_call"] * work["seq"]
                * flops_ssm.base_params(facts["dims"], work["layer_kinds"]))
    least = passes * per_pass / facts["peaks"]["flops_bf16"]
    facts.setdefault("notes", []).append({
        "note": f"roofline of scope {scope} ({phase})", "passes": passes,
        "flop_a_pass": per_pass, "least_s": least, "spent_s": spent})
    return 100.0 * least / spent
