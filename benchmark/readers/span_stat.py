"""One statistic over the program's own spans (``obs/trace.py``'s
in-memory record: name, id, parent, perf_counter endpoints — the clock
of the run's ``t0`` and ``t1``). ``when``: ``window`` keeps the spans
that lie inside the measured window, ``setup`` those that ended before
it. ``minus``: names of child spans whose time is taken off each span
(what is left is the span's own time and its other children).
``stat``: median, sum or last. ``also``: further names whose sums under
the same ``when`` go on an earlier line."""

import statistics

from benchmark.readers import program_trace as pt


def read(facts, name, stat="median", when="window", minus=(), scale=1.0,
         also=()):
    prog = pt.program()
    if prog is None:
        return None
    t0, t1 = facts["t0"], facts["t1"]

    def wanted(s):
        if when == "setup":
            return s["t1"] <= t0
        return s["t0"] >= t0 and s["t1"] <= t1
    spans = [s for s in prog.RECORD.spans if wanted(s)]
    mine = [s for s in spans if s["name"] == name]
    if not mine:
        return None
    off = {}
    for s in spans:
        if s["name"] in minus and s["parent"] is not None:
            off[s["parent"]] = off.get(s["parent"], 0.0) + s["t1"] - s["t0"]
    values = [s["t1"] - s["t0"] - off.get(s["id"], 0.0) for s in mine]
    if also:
        facts.setdefault("notes", []).append({
            "note": f"spans beside {name} ({when})", "count": len(mine),
            **{n: sum(s["t1"] - s["t0"] for s in spans if s["name"] == n)
               for n in also}})
    value = {"median": statistics.median, "sum": sum,
             "last": lambda v: v[-1]}[stat](values)
    return float(value) * float(scale)
