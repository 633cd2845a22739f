"""What the readers of the program's own names share (ISSUE 24): the
program's in-memory trace record of this very process, the join of a
device event to the scope table of the compiled step, and the phase of
an ``op_name``. The xplane is gone by the time a reader runs, so the
table and the spans are read from ``gke_ray_train_tpu.obs.trace.RECORD``;
a program from before that record existed gives None, and every reader
then returns None (the metric is left out of the line).

The phase is read from the name jax writes, by these rules (the
yardstick's own, not the program's): ``rematted_computation`` is the
recomputed forward; else ``transpose(`` is the backward; else ``jvp(``
is the forward; else none (the optimizer, the accumulation's adds).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

PHASES = ("forward", "recompute", "backward")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def program():
    """``gke_ray_train_tpu.obs.trace`` if it keeps a record, else None."""
    try:
        from gke_ray_train_tpu.obs import trace
    except ImportError:
        return None
    return trace if hasattr(trace, "RECORD") else None


def phase_of(op_name: str) -> Optional[str]:
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return None


def attributed(facts: dict) -> Optional[List[Tuple[float, int, str,
                                                    Optional[str]]]]:
    """[(seconds, events, op_name, scope path or None)] for every
    non-container device operation of the traced slice (the set that
    ``breakdown.device_ops`` sums), or None where there is no trace or
    the program kept no scope table. An event whose instruction is in
    no table has op_name ''. Computed once a run; the first call also
    puts the phase shares on an earlier line."""
    if "scoped_ops" in facts:
        return facts["scoped_ops"]
    trace, prog = facts.get("trace"), program()
    out = None
    if trace and trace.get("op_time") and prog is not None \
            and prog.RECORD.scope_tables:
        table: Dict[str, str] = {}
        for t in prog.RECORD.scope_tables.values():
            table.update(t)
        out = []
        for text, seconds in trace["op_time"].items():
            m = _INSTRUCTION.match(text)
            op_name = table.get(m.group(1), "") if m else ""
            out.append((seconds, trace["op_count"].get(text, 0), op_name,
                        prog.scope_path(op_name)))
        facts.setdefault("notes", []).extend(
            [_phase_note(out, prog), _scope_note(out)])
    facts["scoped_ops"] = out
    return out


def share(ops, keep) -> Optional[float]:
    """100 x time of the operations ``keep(op_name, path)`` holds for,
    over the time of all of them."""
    total = sum(s for s, _, _, _ in ops)
    if not total:
        return None
    return 100.0 * sum(s for s, _, op, path in ops if keep(op, path)) / total


def _phase_note(ops, prog) -> dict:
    def is_optimizer(op, path):
        return phase_of(op) is None and (path or "").startswith("optimizer")
    note = {"note": "device time by phase, % of all operations (sums "
            "to 100)"}
    for phase in PHASES:
        note[phase] = share(ops, lambda op, _p, ph=phase: phase_of(op) == ph)
    note["optimizer"] = share(ops, is_optimizer)
    note["rest"] = share(ops, lambda op, p: phase_of(op) is None
                         and not is_optimizer(op, p))
    note["scope_table_s"] = dict(prog.RECORD.scope_table_s)
    note["instructions_named"] = sum(
        len(t) for t in prog.RECORD.scope_tables.values())
    return note


def _scope_note(ops, top: int = 20) -> dict:
    """Seconds by scope path, split by phase: the table a reader of the
    run looks at first."""
    rows: Dict[str, Dict[str, float]] = {}
    for seconds, _, op, path in ops:
        row = rows.setdefault(path or "(unscoped)", {})
        phase = phase_of(op) or "none"
        row[phase] = row.get(phase, 0.0) + seconds
    order = sorted(rows, key=lambda k: -sum(rows[k].values()))[:top]
    return {"note": "device seconds by scope and phase, largest first",
            "scopes": {k: {p: round(v, 6) for p, v in rows[k].items()}
                       for k in order}}
