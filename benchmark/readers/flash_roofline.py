"""The three flash-attention kernels' share of their roofline: the least
time the chip could take for the calls the trace shows (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from the cell's
shapes, causal pairs only) over the kernels' device time. At sequence
1024 and head size 128 the operations bound it."""

from benchmark import flops


def read(facts):
    trace = facts.get("trace")
    work = facts["work"]
    if not trace or "rows_per_call" not in work:
        return None
    need = flops.flash_call(facts["dims"], work["rows_per_call"], work["seq"])
    peaks = facts["peaks"]
    least = spent = 0.0
    for kind, key in (("fwd", "flash_fwd"), ("dq", "flash_dq"),
                      ("dkv", "flash_dkv")):
        calls = trace["kernel_count"].get(key, 0)
        if not calls:
            continue
        least += calls * max(need[kind]["flops"] / peaks["flops_bf16"],
                             need[kind]["bytes"] / peaks["hbm_bytes_per_s"])
        spent += trace["kernel_time"][key]
    if not spent:
        return None
    return 100.0 * least / spent
