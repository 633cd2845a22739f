"""Share of the traced slice's device-operation time spent in the routed
layer (``ops/moe.py``), all phases: under any ``moe/`` scope, or under
the ``stages`` named (``route``, ``dispatch``, ``experts``, ``combine``,
``shared``). What the routing costs around the products is ``route`` +
``dispatch`` + ``combine``."""

import re

from benchmark.readers import program_trace as pt


def read(facts, stages=None):
    ops = pt.attributed(facts)
    if not ops:
        return None
    names = "|".join(stages) if stages else r"\w+"
    pattern = re.compile(r"(^|/)moe/(" + names + r")(/|$)")
    return pt.share(ops, lambda _op, path: bool(
        path and pattern.search(path)))
