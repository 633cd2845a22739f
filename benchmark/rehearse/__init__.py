"""The cells at tiny widths, for rehearsals and tests off the chip."""

import importlib
import pkgutil


def shrink_for(workload: str):
    """The ``shrink`` of the rehearsal module (``rehearse/*_tiny.py``)
    whose ``CELL`` is ``workload``, else ``rehearse/tiny.py``'s, which
    knows a dense decoder's keys."""
    for info in pkgutil.iter_modules(__path__):
        if info.name.endswith("_tiny"):
            module = importlib.import_module(f"{__name__}.{info.name}")
            if getattr(module, "CELL", None) == workload:
                return module.shrink
    from benchmark.rehearse.tiny import shrink
    return shrink
