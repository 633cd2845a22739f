"""Set-up as the program recorded it (ISSUE 34): the seconds from the
process' start to the first timed step, by the program's own spans
(``obs/trace.py``'s in-memory record, ``perf_counter`` endpoints: the
clock of the run's ``t0``), of those that lie between the run's start
(``t0 - setup_s``) and the window.

``part``: a span name, or ``unspanned``: ``setup_s`` less the
benchmark's own ``init_s`` less every top-level program span (one whose
parent is not in the record), which is what no span covers: imports,
the backend's start, the rows, the driver's own reads between the
program's calls. ``needs``: names the record has to hold for that
remainder to mean this (a program that records less of its set-up
gives None, not a larger number under the same name). ``pick``: ``sum``
over the part's spans, or ``first``: the earliest one alone.

The first call of a run puts the whole timeline on an earlier line."""

from benchmark.readers import program_trace as pt

# what every span has; its other scalar fields are its attributes, which
# go on the earlier line beside the name's seconds (`step_compile`'s
# `cache`, `step_lower`'s `trace_s` / `to_mlir_s`, ...)
STAMP = ("name", "id", "parent", "t0", "t1", "step")


def timeline(facts, prog):
    """{"spans": those before the window, oldest first, "unspanned":
    seconds}; computed, and put on an earlier line, once a run."""
    if "setup_timeline" in facts:
        return facts["setup_timeline"]
    t0 = facts["t0"]
    start = t0 - facts["setup_s"]    # (an earlier run of this process,
    spans = sorted(                  # a rehearsal's, is not this set-up)
        (s for s in prog.RECORD.spans if start <= s["t0"] and s["t1"] <= t0),
        key=lambda s: s["t0"])
    ids = {s["id"] for s in spans}
    top = [s for s in spans if s["parent"] not in ids]
    init_s = (facts.get("spans") or {}).get("init_s") or 0.0
    unspanned = (facts["setup_s"] - init_s
                 - sum(s["t1"] - s["t0"] for s in top))
    if spans:
        facts.setdefault("notes", []).append({
            "note": "set-up as the program recorded it, seconds before "
                    "the window", "setup_s": facts["setup_s"],
            "init_s": init_s, "unspanned_s": unspanned,
            "process_start_to_first_program_span_s":
                spans[0]["t0"] - start,
            "top_level": [s["name"] for s in top],
            "gaps_s": _gaps(top, start, t0), "spans": _by_name(spans)})
    facts["setup_timeline"] = {"spans": spans, "unspanned": unspanned}
    return facts["setup_timeline"]


def _by_name(spans):
    """{name: seconds, count and each span's scalar attributes}."""
    out = {}
    for s in spans:
        row = out.setdefault(s["name"], {"s": 0.0, "count": 0})
        row["s"] += s["t1"] - s["t0"]
        row["count"] += 1
        detail = {k: v for k, v in s.items() if k not in STAMP
                  and not isinstance(v, (dict, list, tuple))}
        if detail:
            row.setdefault("each", []).append(detail)
    return out


def _gaps(top, start, t0):
    """What the driver did between the program's top-level calls:
    [after, before, seconds]; they sum to unspanned + init_s."""
    gaps, after, t = [], "process start", start
    for s in top:
        gaps.append([after, s["name"], s["t0"] - t])
        after, t = s["name"], s["t1"]
    return gaps + [[after, "window", t0 - t]]


def read(facts, part, pick="sum", needs=()):
    prog = pt.program()
    if prog is None:
        return None
    line = timeline(facts, prog)
    names = {s["name"] for s in line["spans"]}
    if part == "unspanned":
        return line["unspanned"] if names and set(needs) <= names else None
    mine = [s["t1"] - s["t0"] for s in line["spans"] if s["name"] == part]
    if not mine:
        return None
    return float(mine[0] if pick == "first" else sum(mine))
