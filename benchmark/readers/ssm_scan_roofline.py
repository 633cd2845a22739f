"""The selective scan's share of its roofline, forward only: the least
time the chip could take for the scans of the micro-passes the trace
shows, over the device time of the forward operations under ``ssm/scan``.
A position of a state-space layer is billed the larger of its operations
over peak bf16 FLOP/s and its bytes over peak HBM bytes/s
(``flops_ssm.scan_position``: the chunked form at the published chunk;
the same work whatever chunk or kernel implements it); one pass runs
rows x seq positions through every state-space layer. The passes are
counted from the trace as ``scope_roofline`` counts them."""

import re

from benchmark import flops_ssm
from benchmark.readers import program_trace as pt


def read(facts, scope="(^|/)ssm/scan(/|$)", phase="forward",
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
    need = flops_ssm.scan_position(facts["dims"])
    peaks = facts["peaks"]
    layers = sum(kind == "mamba" for kind, _ in work["layer_kinds"])
    per_pass = work["rows_per_call"] * work["seq"] * layers * max(
        need["flops"] / peaks["flops_bf16"],
        need["bytes"] / peaks["hbm_bytes_per_s"])
    least = passes * per_pass
    facts.setdefault("notes", []).append({
        "note": f"roofline of scope {scope} ({phase})", "passes": passes,
        "position_layer": need, "least_s": least, "spent_s": spent})
    return 100.0 * least / spent
