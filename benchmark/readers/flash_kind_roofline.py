"""The flash kernels' share of their roofline in the layers of one
attention kind (``window`` | ``full``: the leaf scope the program opens
inside ``attn/core`` where a model mixes both): the least time the chip
could take for the calls the trace shows, over their device time. A call
is billed the larger of operations over peak FLOP/s and bytes over peak
bytes/s, for the (query, key) pairs its kind really has: within a
document, and within the window in a sliding layer; rows differ, so a
call is billed the mean row of the window's steps. Kernels are told
apart by the ``name=`` the program gives them (``%flash_fwd.3``)."""

import re

from benchmark import flops_moe
from benchmark.readers import program_trace as pt

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
_NAME = re.compile(r"^%?(" + "|".join(KERNELS) + r")[.\d]* = ")


def read(facts, kind):
    ops = pt.attributed(facts)
    work = facts.get("work") or {}
    trace = facts.get("trace")
    if not ops or not trace or "layer_kinds" not in work:
        return None
    # op text by (seconds, events): the attributed list drops the text,
    # so join again on the instruction the same way it did
    scope = re.compile(r"(^|/)attn/core/" + kind + "(/|$)")
    table = {}
    for text, seconds in trace["op_time"].items():
        m = _NAME.match(text)
        if m:
            table[text] = (m.group(1), seconds, trace["op_count"].get(text, 0))
    if not table:
        return None
    prog = pt.program()
    names = {}
    for t in prog.RECORD.scope_tables.values():
        names.update(t)
    docs = [n for step in work["step_docs"] for n in step]
    rows = len(work["step_docs"]) * work["micro_steps"] \
        * work["rows_per_call"]
    pairs = flops_moe.attention_pairs(
        docs, work["window"] if kind == "window" else None) / max(rows, 1)
    need = flops_moe.flash_call(facts["dims"], work["rows_per_call"],
                                work["seq"], pairs * work["rows_per_call"])
    peaks = facts["peaks"]
    devices = max(int(trace.get("devices", 1)), 1)
    least = spent = 0.0
    for text, (kernel, seconds, events) in table.items():
        instr = pt._INSTRUCTION.match(text)
        path = prog.scope_path(names.get(instr.group(1), "")) if instr \
            else None
        if not path or not scope.search(path):
            continue
        least += events / devices * max(
            need[kernel]["flops"] / peaks["flops_bf16"],
            need[kernel]["bytes"] / peaks["hbm_bytes_per_s"])
        spent += seconds
    if not spent:
        return None
    facts.setdefault("notes", []).append({
        "note": f"flash kernels in {kind} layers", "pairs_a_row": pairs,
        "least_s": least, "spent_s": spent})
    return 100.0 * least / spent
