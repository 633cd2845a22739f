"""From an ``.xplane.pb`` to numbers: device busy time, time by operation
and by kernel, collectives not covered by compute, and the longest idle
gaps named by the benchmark's own host span that covers each. Needs
nothing but JAX's ``ProfileData``. Checked against the small recorded
trace in ``benchmark/tests/data`` (tests/test_trace.py).

A Pallas kernel carries no name in the trace today, so each is known by
the shapes of its results and operands: one file a kernel under
``benchmark/kernels/``, a pattern whose ``<rows>``, ``<seq>``, ... are
filled from the cell's own numbers. A kernel's event that matches no
file is an error: its time is never billed to another kernel.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from benchmark import harness as hs

Interval = Tuple[float, float]


def names() -> dict:
    return hs.load_json(hs.BENCH_DIR, "trace_names.json")


def kernel_patterns(numbers: Dict[str, int]) -> Dict[str, "re.Pattern"]:
    """{kernel: pattern} for the kernels whose numbers this cell has. In
    a file's pattern ``<Q>`` stands for one of its ``shapes`` (with
    whatever layout follows it) and ``<rows>`` for one of ``numbers``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(hs.BENCH_DIR, "kernels",
                                              "*.json"))):
        spec = hs.load_json(path)
        text = spec["pattern"]
        for name, shape in spec["shapes"].items():
            text = text.replace(f"<{name}>", shape + r"(?:\{\S*\})?")
        wanted = set(re.findall(r"<(\w+)>", text))
        if wanted - set(numbers):
            continue
        for name in wanted:
            text = text.replace(f"<{name}>", str(int(numbers[name])))
        out[spec["name"]] = re.compile(text)
    return out


def union_length(intervals: List[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def merged(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def minus(a: List[Interval], b: List[Interval]) -> float:
    """Length of the union of ``a`` that no interval of ``b`` covers."""
    return union_length(a) - _overlap(merged(a), merged(b))


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def matches(name: str, patterns: List[str]) -> bool:
    """Patterns are regular expressions (trace_names.json)."""
    return any(re.search(p, name) for p in patterns)


def op_of(hlo_text: str) -> str:
    """The operation (``fusion``, ``while``, ``custom-call``) of an
    event's HLO text, or ''."""
    _, _, rest = hlo_text.partition(" = ")
    op = re.search(r"(?:^|[ )}\]])([a-z][a-z\-]*)\(", rest)
    return op.group(1) if op else ""


def short_name(hlo_text: str) -> str:
    """``%fusion.12 fusion bf16[8,128]``: the result's name, the
    operation and the first result's shape, out of the event's full HLO
    text."""
    lhs, _, rest = hlo_text.partition(" = ")
    shape = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    return " ".join(x for x in (lhs, op_of(hlo_text),
                                shape.group(1) if shape else "") if x)


def reduce_file(path: str, window: Optional[Interval] = None,
                numbers: Optional[Dict[str, int]] = None) -> dict:
    """All times in seconds. ``window`` is the traced slice on the host's
    perf_counter; its length is the traced window's length (the device's
    clock has another origin, so only the length is taken from it).
    ``numbers`` are the cell's sizes, for the kernels' patterns."""
    from jax.profiler import ProfileData
    nm = names()
    known = kernel_patterns(numbers or {})
    data = ProfileData.from_file(path)
    planes = [p for p in data.planes
              if p.name.startswith(nm["device_plane_prefix"])]
    per_device = []
    op_time: Dict[str, float] = {}
    op_count: Dict[str, int] = {}
    kernel_time = {k: 0.0 for k in known}
    kernel_count = {k: 0 for k in known}
    exposed, gaps_all = [], []
    # event name -> (collective?, kernels, container?)
    kinds: Dict[str, tuple] = {}
    strangers = set()
    for plane in planes:
        ops: List[Interval] = []
        coll: List[Interval] = []
        compute: List[Interval] = []
        for line in plane.lines:
            if line.name in nm["op_lines"]:
                for e in line.events:
                    iv = (e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                          * 1e-9)
                    ops.append(iv)
                    name = e.name
                    if name not in kinds:
                        kinds[name] = (
                            matches(name, nm["collective_ops"]),
                            [k for k, pat in known.items()
                             if pat.search(name)],
                            op_of(name) in nm["container_ops"])
                        if nm["kernel_call"] in name and not kinds[name][1]:
                            strangers.add(name)
                    is_coll, kernels, container = kinds[name]
                    if container:
                        # a loop's event spans the events of its body:
                        # it counts as busy time, not as an operation
                        continue
                    op_time[name] = op_time.get(name, 0.0) + iv[1] - iv[0]
                    op_count[name] = op_count.get(name, 0) + 1
                    (coll if is_coll else compute).append(iv)
                    for k in kernels:
                        kernel_time[k] += iv[1] - iv[0]
                        kernel_count[k] += 1
        if not ops:
            continue
        busy = merged(ops)
        per_device.append({"busy_s": union_length(ops),
                           "first": busy[0][0], "last": busy[-1][1]})
        exposed.append(minus(coll, compute))
        gaps_all.append([(b[0] - a[1], a[1], b[0])
                         for a, b in zip(busy, busy[1:])])
    if strangers:
        raise hs.BenchFailure(
            "the trace holds kernels that no file of benchmark/kernels/ "
            "matches at this cell's sizes; add a file for each: "
            + " | ".join(sorted(x[:600] for x in strangers)))
    n = max(len(per_device), 1)
    busy_s = sum(d["busy_s"] for d in per_device) / n
    if window is not None and window[0] is not None \
            and window[1] is not None:
        window_s = window[1] - window[0]
    elif per_device:
        window_s = max(d["last"] for d in per_device) - min(
            d["first"] for d in per_device)
    else:
        window_s = 0.0
    spans = host_spans(data, nm)
    gaps = name_gaps(gaps_all[0] if gaps_all else [], spans)
    scale = 1.0 / n
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    label = {k: short_name(k) + "".join(
        " [" + x + "]" for x in kinds[k][1]) for k, _ in top_ops}
    return {
        "busy_s": busy_s, "window_s": max(window_s, busy_s),
        "devices": len(per_device),
        "op_time": {k: v * scale for k, v in op_time.items()},
        "op_count": op_count,
        "kernel_time": {k: v * scale for k, v in kernel_time.items()},
        "kernel_count": {k: v // n for k, v in kernel_count.items()},
        "collective_exposed_s": sum(exposed) / n if exposed else 0.0,
        "breakdown": {
            "device_ops": [[label[k], v * scale] for k, v in top_ops],
            "idle_gaps": gaps},
    }


def host_spans(data, nm) -> List[Tuple[float, float, str]]:
    """The benchmark's own spans (TraceAnnotation) on the host plane."""
    out = []
    for plane in data.planes:
        if plane.name != nm["host_plane"]:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(nm["span_prefix"]):
                    out.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                e.name[len(nm["span_prefix"]):]))
    return out


def name_gaps(gaps, spans) -> List[list]:
    """Idle seconds by the host span that covers most of each gap; the
    ten largest sums. Device and host events share the trace's clock."""
    by_name: Dict[str, float] = {}
    for length, a, b in gaps:
        best, cover = "no_benchmark_span", 0.0
        for s0, s1, name in spans:
            c = min(b, s1) - max(a, s0)
            if c > cover:
                best, cover = name, c
        by_name[best] = by_name.get(best, 0.0) + length
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return [[k, v] for k, v in top]


def reduce_dir(trace_dir: str, window: Optional[Interval],
               require_device: bool = True,
               numbers: Optional[Dict[str, int]] = None) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise hs.BenchFailure(f"the profiler wrote no trace to {trace_dir}")
    out = reduce_file(paths[-1], window, numbers)
    if require_device and out["busy_s"] <= 0:
        raise hs.BenchFailure("the trace holds no device operation")
    return out
