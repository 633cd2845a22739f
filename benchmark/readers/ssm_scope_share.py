"""Share of the traced slice's device-operation time spent in the
state-space layers' mixers (``models/transformer.py::_ssm``), all
phases: under any ``ssm/`` scope, or under the ``stages`` named
(``in_proj``, ``conv``, ``scan``, ``gate_norm``, ``out_proj``). What is
not a projection is ``conv`` + ``scan`` + ``gate_norm``. A program
without such a scope gives nothing to read."""

import re

from benchmark.readers import program_trace as pt


def read(facts, stages=None):
    ops = pt.attributed(facts)
    if not ops:
        return None
    names = "|".join(stages) if stages else r"\w+"
    pattern = re.compile(r"(^|/)ssm/(" + names + r")(/|$)")
    if not any(path and pattern.search(path) for _, _, _, path in ops):
        return None
    return pt.share(ops, lambda _op, path: bool(
        path and pattern.search(path)))
