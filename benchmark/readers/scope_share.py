"""Share of the traced slice's device-operation time that lies in one
phase and/or under one scope of the program's vocabulary
(``benchmark/readers/program_trace.py``). ``phase``: forward, recompute
or backward. ``scope``: a pattern searched in the scope path
(``attn/qkv/base``). ``unscoped``: instead, the time the attribution
cannot name: the instruction is in no table, or its ``op_name`` holds
no name of the vocabulary."""

import re

from benchmark.readers import program_trace as pt


def read(facts, phase=None, scope=None, unscoped=False):
    ops = pt.attributed(facts)
    if not ops:
        return None
    if unscoped:
        return pt.share(ops, lambda _op, path: path is None)
    pattern = re.compile(scope) if scope else None
    return pt.share(ops, lambda op, path: (
        (phase is None or pt.phase_of(op) == phase)
        and (pattern is None or bool(path and pattern.search(path)))))
