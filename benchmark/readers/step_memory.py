"""The step's memory as XLA laid it out, beside what the program's
chooser expected (ISSUE 34): both from the ``step_build`` span of the
step the window ran, the last one built before it (after a fallback the
span carries the fallback's executable and estimate).

``what``: ``unused_share``, 100 x (limit - peak) / limit of
``xla_memory``: the HBM XLA's peak leaves under the device's own limit;
``estimate_gap_gb``: (``remat_estimate_bytes`` - ``xla_memory.peak``) /
1e9, signed: the room the block checkpoints were not given because the
estimate stood over XLA's peak, negative where it erred low. None where
the program records neither, or the device reports no limit.

The first call of a run puts the span's memory on an earlier line."""

from benchmark.readers import program_trace as pt

NOTED = ("remat_keep", "remat_keep_bytes", "remat_budget_bytes",
         "remat_args_bytes", "remat_keep_fallback",
         "remat_estimate_bytes", "xla_memory")


def built_step(facts, prog):
    """The ``step_build`` span of the step the window ran, or None."""
    if "built_step" in facts:
        return facts["built_step"]
    spans = [s for s in prog.RECORD.spans
             if s["name"] == "step_build" and s["t1"] <= facts["t0"]]
    span = max(spans, key=lambda s: s["t1"]) if spans else None
    if span is not None and span.get("xla_memory"):
        facts.setdefault("notes", []).append({
            "note": "the step's memory: XLA's layout beside the "
                    "chooser's estimate, bytes",
            **{k: span[k] for k in NOTED if k in span}})
    facts["built_step"] = span
    return span


def read(facts, what):
    prog = pt.program()
    if prog is None:
        return None
    span = built_step(facts, prog)
    memory = (span or {}).get("xla_memory") or {}
    peak = memory.get("peak")
    if not peak:
        return None
    if what == "unused_share":
        limit = memory.get("limit")
        return 100.0 * (limit - peak) / limit if limit else None
    estimate = span.get("remat_estimate_bytes")
    return None if estimate is None else (estimate - peak) / 1e9
