"""Compile-level step cost accounting — numbers that need no accelerator.

``StepCostReport`` extracts XLA's own ledger from an AOT-compiled
executable: ``cost_analysis()`` (flops, bytes accessed),
``memory_analysis()`` (peak temp / argument / output / aliased bytes)
and the optimized HLO text (collective count & bytes by kind). All of
it comes from lowering + compilation alone, so the identical report is
produced on the 8-fake-device CPU mesh CI runs on and on a v5e-16 —
which is what makes the budget harness (:mod:`perf.budget`) a tier-1
regression gate rather than a hardware benchmark.

Numbers describe the **per-device SPMD program** XLA compiled (under
GSPMD the compiled module is the per-device partition; flops/bytes are
that partition's). The analytic MFU ceiling is the classic roofline:
``t_compute = flops / peak_flops``, ``t_hbm = bytes / hbm_bw``, ceiling
= ``t_compute / max(t_compute, t_hbm)`` at a given chip spec.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import jax


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops: float        # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float   # HBM bandwidth per chip
    hbm_bytes: float         # HBM capacity per chip
    vmem_bytes: float = 16 * 2**20   # on-chip vector memory per core
    # network: intra-slice ICI vs the inter-slice data-center fabric.
    # Nominal per-chip figures (order-of-magnitude, like the cpu spec's
    # flops) — what matters for the roofline is the RATIO: DCN is
    # ~1.5 orders of magnitude slower than ICI, which is why a flat
    # all-reduce whose full payload crosses slices dominates step time
    # on multi-slice pools and why hier_psum sends 1/ici_size of it.
    ici_bytes_per_s: float = 100e9
    dcn_bytes_per_s: float = 6.25e9   # ~50 Gbit/s per chip share


CHIP_SPECS = {
    "v5e": ChipSpec("v5e", 197e12, 819e9, 16 * 2**30,
                    ici_bytes_per_s=200e9),
    "v5p": ChipSpec("v5p", 459e12, 2765e9, 95 * 2**30,
                    ici_bytes_per_s=600e9),
    "v4": ChipSpec("v4", 275e12, 1228e9, 32 * 2**30,
                   ici_bytes_per_s=300e9),
    "v6e": ChipSpec("v6e", 918e12, 1640e9, 32 * 2**30,
                    ici_bytes_per_s=400e9),
    # nominal CPU spec: keeps ceilings finite for the CI mesh; vmem uses
    # the TPU figure so kernelcheck KER002 verdicts match real chips
    "cpu": ChipSpec("cpu", 1e12, 50e9, 8 * 2**30,
                    ici_bytes_per_s=10e9, dcn_bytes_per_s=1e9),
}

# device_kind substring → spec key (same matching discipline as
# train.metrics.PEAK_FLOPS; longest key wins)
_KIND_TO_SPEC = {
    "v5 lite": "v5e", "v5e": "v5e", "v5p": "v5p", "v5": "v5p",
    "v4": "v4", "v6 lite": "v6e", "v6e": "v6e", "cpu": "cpu",
}


def chip_spec_for_devices() -> ChipSpec:
    """Spec of the attached device; an unknown ``device_kind`` raises
    (same rule as ``train.metrics.peak_flops_per_device``)."""
    kind = jax.devices()[0].device_kind.lower()
    for k, spec in sorted(_KIND_TO_SPEC.items(), key=lambda kv: -len(kv[0])):
        if k in kind:
            return CHIP_SPECS[spec]
    raise ValueError(
        f"device_kind {kind!r} matches no chip spec "
        f"({sorted(_KIND_TO_SPEC)}); add it to {__name__}")


COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32"
                       r"|s64|u64|c64|c128)\[([0-9,]*)\]")
# "<result-type> <kind>(" — also matches async "-start" forms; "-done"
# deliberately does not match (it would double-count the async pair)
_COLL_RE = re.compile(
    r"=\s*(.*?)\s(" + "|".join(COLLECTIVE_KINDS) + r")(?:-start)?\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


# ---------------------------------------------------------------------------
# while-loop trip counts: a collective inside a scan body appears ONCE
# in the HLO text but executes once PER TRIP — static byte accounting
# that ignores the multiplier under-reports a grad-accum or layer-scan
# program by the scan length (the PR-11 caveat, fixed here)
# ---------------------------------------------------------------------------

# a while op names its body computation and (XLA's loop analysis
# willing) its statically-known trip count in backend_config. A while
# can be the computation ROOT (a step whose entry or outer body
# returns only the scan carry) — the prefix must not hide it.
_WHILE_RE = re.compile(r"(?:ROOT\s+)?%[\w.\-]+ = [^\n]*?\bwhile\([^\n]*")
_WHILE_BODY_RE = re.compile(r"body=%([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')


def _while_trip_counts(hlo_text: str,
                       comps: Optional[List[Tuple[str, List[str]]]] = None
                       ) -> Dict[str, int]:
    """computation name -> executions per step, for every while BODY
    whose trip count XLA proved statically (``known_trip_count``; a
    ``compare(iv, constant), direction=LT`` condition is the fallback).
    Nested loops compose: a body inside a body multiplies through. An
    unknown trip count conservatively counts once (the pre-fix
    behavior), never guesses. ``comps``: a precomputed
    :func:`_computation_lines` split (``step_cost_report`` parses the
    — potentially multi-MB — HLO text once and shares it)."""
    if comps is None:
        comps = _computation_lines(hlo_text)
    # (containing computation, body name, trips) per while op
    whiles: List[Tuple[str, str, Optional[int]]] = []
    cond_of: Dict[str, str] = {}
    for comp, comp_lines in comps:
        for line in comp_lines:
            s = line.strip()
            if not _WHILE_RE.match(s):
                continue
            bm = _WHILE_BODY_RE.search(s)
            if bm is None:
                continue
            tm = _TRIP_RE.search(s)
            trips = int(tm.group(1)) if tm else None
            whiles.append((comp, bm.group(1), trips))
            cm = re.search(r"condition=%([\w.\-]+)", s)
            if cm:
                cond_of[bm.group(1)] = cm.group(1)
    if not whiles:
        return {}
    # fallback trip parse: the condition computation's
    # `compare(iv, constant(N)), direction=LT` — the lax.scan shape
    unresolved = [b for _, b, t in whiles if t is None]
    cond_trips: Dict[str, int] = {}
    if unresolved:
        consts: Dict[str, Dict[str, int]] = {}
        lt: Dict[str, List[str]] = {}
        for comp, comp_lines in comps:
            for line in comp_lines:
                s = line.strip()
                cm = re.match(r"(?:ROOT\s+)?%([\w.\-]+) = s32\[\] "
                              r"constant\((\d+)\)", s)
                if cm:
                    consts.setdefault(comp, {})[cm.group(1)] = \
                        int(cm.group(2))
                if "direction=LT" in s and " compare(" in s:
                    lt.setdefault(comp, []).extend(
                        re.findall(r"%([\w.\-]+)", s))
        for body, cond in cond_of.items():
            operands = lt.get(cond, ())
            vals = [consts.get(cond, {}).get(o) for o in operands]
            vals = [v for v in vals if v is not None]
            if len(vals) == 1:
                cond_trips[body] = vals[0]
    # compose nesting: multiplier(body) = trips x multiplier(container)
    mult: Dict[str, int] = {}
    trips_of = {b: (t if t is not None else cond_trips.get(b))
                for _, b, t in whiles}
    container = {b: c for c, b, _ in whiles}
    for body in trips_of:
        m, seen, b = 1, set(), body
        while b in trips_of and b not in seen:
            seen.add(b)
            t = trips_of[b]
            if t is None:
                break
            m *= t
            b = container[b]
        mult[body] = m
    return {b: m for b, m in mult.items() if m > 1}


# ---------------------------------------------------------------------------
# replica-group parsing: which DEVICES a collective spans — the input
# to the ICI/DCN byte attribution (a group that crosses a slice
# boundary pays data-center-network latency, not ICI)
# ---------------------------------------------------------------------------

_RG_IOTA_RE = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")


def _replica_groups(line: str) -> Optional[List[List[int]]]:
    """Parse an HLO collective line's replica groups. Handles the
    explicit ``{{0,1},{2,3}}`` form, the iota ``[2,4]<=[8]`` (optionally
    ``T(perm)``-transposed) form, and collective-permute's
    ``source_target_pairs``. Returns None when the line carries no
    group syntax at all; ``[[]]`` (one empty group) means "all
    devices" per HLO semantics."""
    m = _RG_IOTA_RE.search(line)
    if m:
        dims = [int(d) for d in m.group(1).split(",")]
        reshape = [int(d) for d in m.group(2).split(",")]
        total = 1
        for d in reshape:
            total *= d
        ids = list(range(total))
        if m.group(3):
            import numpy as np
            perm = [int(p) for p in m.group(3).split(",")]
            ids = list(np.arange(total).reshape(reshape)
                       .transpose(perm).flatten())
        group_size = 1
        for d in dims[1:]:
            group_size *= d
        return [list(map(int, ids[i:i + group_size]))
                for i in range(0, total, group_size)]
    m = re.search(r"replica_groups=\{((?:\{[0-9, ]*\},?)*)\}", line)
    if m is not None:
        groups = [[int(x) for x in g.split(",") if x.strip()]
                  for g in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
        if groups:
            return groups
        return [[]]   # replica_groups={} = one group of every device
    m = _PAIRS_RE.search(line)
    if m is not None:
        return [[int(x) for x in g.split(",") if x.strip()]
                for g in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
    return None


def _crosses_slices(groups: Optional[List[List[int]]],
                    slice_map: List[int]) -> bool:
    """Does any replica group span more than one slice? Group ids are
    positions in the program's device assignment; for the hybrid mesh
    contract (slices are the OUTERMOST, contiguous blocks of the
    flattened mesh — both ``create_hybrid_device_mesh`` and the
    emulated fake-device layout, pinned in test_mesh.py) that position
    maps to a slice via ``slice_map``."""
    if not slice_map or len(set(slice_map)) <= 1:
        return False
    if not groups:
        return False
    for g in groups:
        members = g if g else range(len(slice_map))
        seen = {slice_map[i] for i in members if i < len(slice_map)}
        if len(seen) > 1:
            return True
    return False


def collective_stats(hlo_text: str, *,
                     _comps=None, _trips=None
                     ) -> Tuple[Dict[str, int], int, List[str]]:
    """(count-by-kind, total result bytes, matched HLO lines) for every
    collective in an optimized HLO module. The lines ride along so a
    budget miss can print the actual offending ops, not just a count.

    Bytes are weighted by the statically-known while-loop trip count of
    the computation the op sits in (a collective in a 2-layer scan body
    executes twice per step); COUNTS stay static op counts — the
    exact-count check is about program structure, the byte ledger about
    runtime traffic. Trip-weighted lines carry an ``// x<N>`` suffix.

    ``_comps``/``_trips``: precomputed computation split / trip map —
    ``step_cost_report`` parses the HLO text once and shares it with
    all three analyses (a real-model scheduled dump is multi-MB)."""
    counts = {k: 0 for k in COLLECTIVE_KINDS}
    total_bytes = 0
    lines: List[str] = []
    comps = _comps if _comps is not None else _computation_lines(hlo_text)
    trips = _trips if _trips is not None \
        else _while_trip_counts(hlo_text, comps)
    for comp, comp_lines in comps:
        mult = trips.get(comp, 1)
        for line in comp_lines:
            m = _COLL_RE.search(line)
            if m is None:
                continue
            counts[m.group(2)] += 1
            total_bytes += _shape_bytes(m.group(1)) * mult
            tag = f" // x{mult} while-trip" if mult > 1 else ""
            lines.append(line.strip()[:200] + tag)
    return counts, total_bytes, lines


def collective_axis_stats(hlo_text: str, slice_map: List[int], *,
                          _comps=None, _trips=None
                          ) -> Tuple[int, int, List[str]]:
    """(ici_bytes, dcn_bytes, dcn attribution lines): every
    collective's result bytes attributed to the interconnect its
    replica groups span — intra-slice ICI, or DCN when a group crosses
    the slice boundary. Trip-weighted like :func:`collective_stats`
    (and sharing its precomputed-parse convention). With a
    single-slice (or empty) ``slice_map`` everything is ICI by
    construction."""
    ici = 0
    dcn = 0
    lines: List[str] = []
    comps = _comps if _comps is not None else _computation_lines(hlo_text)
    trips = _trips if _trips is not None \
        else _while_trip_counts(hlo_text, comps)
    for comp, comp_lines in comps:
        mult = trips.get(comp, 1)
        for line in comp_lines:
            m = _COLL_RE.search(line)
            if m is None:
                continue
            nbytes = _shape_bytes(m.group(1)) * mult
            groups = _replica_groups(line)
            if _crosses_slices(groups, slice_map):
                dcn += nbytes
                n_slices = len(set(slice_map))
                lines.append(
                    f"{m.group(2)} {nbytes}B crosses the slice boundary "
                    f"(replica groups span {n_slices} slices"
                    + (f"; x{mult} while-trip" if mult > 1 else "")
                    + "): " + line.strip()[:140])
            else:
                ici += nbytes
    return ici, dcn, lines


def _computation_lines(hlo_text: str) -> List[Tuple[str, List[str]]]:
    """(computation name, raw op lines) per computation, in file
    order — the shared walk collective_stats / collective_axis_stats
    attribute trip counts through. Lines outside any computation
    header land in an implicit ``""`` fragment (multiplier 1), so bare
    HLO snippets — unit-test fixtures — still parse."""
    out: List[Tuple[str, List[str]]] = []
    cur: List[str] = []
    name = ""
    in_comp = False
    for line in hlo_text.splitlines():
        s = line.strip()
        if not in_comp:
            if s.endswith("{") and ("->" in s or s.startswith("ENTRY")):
                if cur:
                    out.append((name, cur))
                m = re.match(r"(?:ENTRY\s+)?%([\w.\-]+)", s)
                name = m.group(1) if m else "?"
                cur = []
                in_comp = True
            else:
                cur.append(line)
            continue
        if s == "}" or line.startswith("}"):
            out.append((name, cur))
            cur = []
            name = ""
            in_comp = False
            continue
        cur.append(line)
    if cur:
        out.append((name, cur))
    return out


# ---------------------------------------------------------------------------
# overlap / exposure analysis of the scheduled entry computation
# ---------------------------------------------------------------------------

_ENTRY_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_COMPUTE_KINDS = ("dot", "convolution", "fusion", "custom-call")
_COMPUTE_RE = re.compile(
    r"^(.*?)\s(" + "|".join(_COMPUTE_KINDS) + r")\(")


def _computations(hlo_text: str
                  ) -> List[Tuple[str, List[Tuple[str, str]], bool]]:
    """Per-computation (comp_name, [(name, rhs)], is_entry) triples, in
    schedule order (the optimized module prints each computation's ops
    in the order the scheduler chose). Collectives live in the ENTRY
    computation AND in loop bodies (a scanned grad-accum step keeps its
    collectives inside the while body), so exposure is analyzed per
    computation — and the carried-to-root classification needs to know
    which root is a LOOP carry vs the program output."""
    comps: List[Tuple[str, List[Tuple[str, str]], bool]] = []
    cur: Optional[List[Tuple[str, str]]] = None
    comp_name = ""
    is_entry = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if cur is None:
            # "%comp (args) -> type {" or "ENTRY %main (...) -> ... {"
            if stripped.endswith("{") and ("->" in stripped
                                           or stripped.startswith("ENTRY")):
                cur = []
                is_entry = stripped.startswith("ENTRY")
                m = re.match(r"(?:ENTRY\s+)?%([\w.\-]+)", stripped)
                comp_name = m.group(1) if m else "?"
            continue
        if stripped == "}" or line.startswith("}"):
            comps.append((comp_name, cur, is_entry))
            cur = None
            continue
        m = _ENTRY_OP_RE.match(line)
        if m:
            cur.append((m.group(1), m.group(2)))
    if cur:
        comps.append((comp_name, cur, is_entry))
    return comps


def overlap_stats(hlo_text: str, *,
                  _trips=None) -> Tuple[int, float, List[str]]:
    """(exposed_collective_bytes, overlap_frac, attribution lines).

    Walks the scheduled computations and classifies every collective as
    *hidden* or *EXPOSED*. Three ways to be hidden, all bytes-weighted
    (a window must hold at least the collective's own result bytes of
    independent compute — a 1-op window cannot mask a multi-MB
    all-gather):

    - an async ``-start``/``-done`` pair with enough independent
      compute scheduled inside the window;
    - a synchronous collective *scheduled ahead of its first consumer*
      with enough independent compute in the gap (the latency-hiding
      schedule already moved it — dataflow through copies / bitcasts /
      tuples / opt-barriers is resolved, so a fence does not count as
      a consumer);
    - a synchronous collective whose result is consumed only by the
      NEXT loop iteration (it flows to the while body's root tuple —
      the double-buffered prefetch shape ``train/overlap.py`` emits:
      layer *k+1*'s all-gather is issued while layer *k* computes, so
      the whole body's independent compute is available to hide it.
      The CPU list scheduler shows no async pair, but the *dataflow*
      is schedule-independent — an async runtime (TPU DMA engines,
      XLA's latency-hiding scheduler) overlaps a carried collective by
      construction, which is what lets CPU-mesh budgets assert the
      overlap claim while the accelerator backend is dark).

    Everything else is EXPOSED (the step stalls for the full fabric
    latency); the attribution line reports ``hidden_compute_bytes`` —
    the independent compute (neither ancestor nor descendant) a
    latency-hiding schedule COULD move into its window. That number is
    the actionable half: ``exposed > 0`` with independent compute
    available is exactly the overlap opportunity ROADMAP #3 asserts
    through budgets.

    ``overlap_frac`` = hidden bytes / total collective bytes (1.0 when
    the program has no collectives — nothing is exposed). Both sides
    are weighted by the statically-known while-trip count of the
    computation (a collective in a 2-trip scan body is executed — and
    exposed or hidden — twice per step; scaling exposed and total
    together keeps the frac a per-execution property)."""
    exposed = 0
    total = 0
    lines: List[str] = []
    trips = _trips if _trips is not None else _while_trip_counts(hlo_text)
    for comp_name, ops, is_entry in _computations(hlo_text):
        e, t, ls = _overlap_in_computation(ops, is_entry=is_entry)
        mult = trips.get(comp_name, 1)
        exposed += e * mult
        total += t * mult
        lines.extend(ls if mult == 1
                     else [f"{ln} // x{mult} while-trip" for ln in ls])
    frac = 1.0 if total == 0 else round(1.0 - exposed / total, 6)
    return exposed, frac, lines


# ops that move/regroup data without computing: dataflow is resolved
# THROUGH them when finding a collective's real consumers (a copy or a
# scheduling fence between a prefetched all-gather and the loop root
# must not read as "consumed immediately")
_PASSTHROUGH_KINDS = frozenset({
    "copy", "bitcast", "tuple", "get-tuple-element", "opt-barrier",
    "optimization-barrier"})
_ROOT = "#root"   # sentinel consumer: the computation's root tuple


def _overlap_in_computation(ops: List[Tuple[str, str]], *,
                            is_entry: bool = False
                            ) -> Tuple[int, int, List[str]]:
    index = {name: i for i, (name, _) in enumerate(ops)}
    deps: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = {n: [] for n, _ in ops}
    kind_of: Dict[str, str] = {}
    for name, rhs in ops:
        # the opcode is the first WHITESPACE-PRECEDED word directly
        # followed by "(" — result types never contain one, a
        # tuple-typed result's leading "(f32[...], ...)" holds no such
        # pair, and TPU tile-layout annotations ("{1,0:T(8,128)}")
        # prepend ":" not whitespace, so they can't shadow the opcode
        km = re.search(r"(?<=\s)([\w\-]+)\(", rhs)
        kind_of[name] = km.group(1) if km else ""
        paren = rhs.find(" " + kind_of[name] + "(") if km else -1
        body = rhs[paren:] if paren >= 0 else rhs
        deps[name] = [d for d in re.findall(r"%([\w.\-]+)", body)
                      if d in index and d != name]
        for d in deps[name]:
            users[d].append(name)
    root = ops[-1][0] if ops else None

    def reach(name: str, edges: Dict[str, List[str]]) -> set:
        """Transitive closure from ONE op — two walks per collective
        (ancestors via deps, descendants via users) keep the whole
        analysis O(#collectives x E) instead of materializing a
        closure per op (a non-tiny step module has 10^4+ ops and this
        runs inside every step_cost_report)."""
        out: set = set()
        stack = list(edges.get(name, ()))
        while stack:
            d = stack.pop()
            if d in out:
                continue
            out.add(d)
            stack.extend(edges.get(d, ()))
        return out

    compute: Dict[str, int] = {}       # name -> result bytes
    for name, rhs in ops:
        m = _COMPUTE_RE.match(rhs)
        if m:
            compute[name] = _shape_bytes(m.group(1))

    def real_consumers(name: str) -> set:
        """Schedule-independent consumers: dataflow resolved through
        pass-through ops. The computation root maps to the ``_ROOT``
        sentinel — a result that only reaches the root tuple is
        *carried* (consumed by the next loop iteration)."""
        out: set = set()
        stack = list(users.get(name, ()))
        seen: set = set()
        while stack:
            u = stack.pop()
            if u in seen:
                continue
            seen.add(u)
            if kind_of.get(u) in _PASSTHROUGH_KINDS:
                # a pass-through ROOT (the while body's carry tuple)
                # is the "next iteration" sentinel; mid-graph
                # pass-throughs are resolved through
                if u == root:
                    out.add(_ROOT)
                else:
                    stack.extend(users.get(u, ()))
            else:
                out.add(u)
        return out

    # collect collectives: sync ops, and start/done pairs (done's first
    # operand chain leads back to the start op)
    total = 0
    exposed = 0
    lines: List[str] = []
    done_for: Dict[str, Tuple[str, str]] = {}
    rhs_of = dict(ops)
    for name, rhs in ops:
        m = re.search(r"\b(" + "|".join(COLLECTIVE_KINDS) + r")-done\(",
                      rhs)
        if m:
            starts = [d for d in deps[name] if f"{m.group(1)}-start(" in
                      rhs_of.get(d, "")]
            if starts:
                done_for[starts[0]] = (name, rhs)
    for name, rhs in ops:
        m = _COLL_RE.search("= " + rhs if not rhs.startswith("=") else rhs)
        if m is None:
            continue
        kind = m.group(2)
        is_start = f"{kind}-start(" in rhs
        if is_start and name in done_for:
            dname, drhs = done_for[name]
            paren = drhs.find(f"{kind}-done(")
            nbytes = _shape_bytes(drhs[:paren])
            desc = reach(name, users)
            window = [w for w, _ in ops[index[name] + 1:index[dname]]
                      if w in compute and w not in desc]
            hidden = sum(compute[w] for w in window)
            total += nbytes
            # bytes-weighted: the window must hold at least the
            # collective's own bytes of independent compute
            if hidden >= nbytes and hidden > 0:
                lines.append(
                    f"{kind} {nbytes}B hidden behind {len(window)} "
                    f"compute op(s) (~{hidden}B results) in its "
                    "start/done window")
                continue
            exposed += nbytes
            if hidden > 0:
                lines.append(
                    f"{kind} {nbytes}B EXPOSED (async window holds only "
                    f"~{hidden}B of independent compute across "
                    f"{len(window)} op(s) — a thin window cannot hide "
                    f"{nbytes}B)")
            else:
                lines.append(f"{kind} {nbytes}B EXPOSED (async pair "
                             "with an empty window)")
            continue
        nbytes = _shape_bytes(m.group(1))
        total += nbytes
        desc = reach(name, users)
        anc = reach(name, deps)
        consumers = real_consumers(name)
        if consumers <= {_ROOT} and not is_entry:
            # carried: the result flows only to a NON-ENTRY root tuple
            # (a while-body carry) — the next iteration consumes it, so
            # every independent op of this body can hide it (the
            # double-buffered prefetch shape). In ENTRY the root IS the
            # program output: a collective feeding only it stalls the
            # step before returning and stays EXPOSED below.
            indep_bytes = sum(b for c, b in compute.items()
                              if c != name and c not in desc
                              and c not in anc)
            if indep_bytes >= nbytes and indep_bytes > 0:
                lines.append(
                    f"{kind} {nbytes}B hidden (double-buffered: result "
                    "carried to the next loop iteration; "
                    f"~{indep_bytes}B independent compute in the body "
                    "hides it)")
                continue
        else:
            non_root = [index[c] for c in consumers if c != _ROOT]
            # no real consumer at all (ENTRY-carried: the result feeds
            # only the program output) — nothing downstream ever waits
            # overlapped on it; the step stalls before returning, so it
            # falls through to EXPOSED rather than crediting the whole
            # trailing schedule as a hiding window
            if non_root:
                first = min(non_root)
                window = [w for w, _ in ops[index[name] + 1:first]
                          if w in compute and w not in desc]
                gap_bytes = sum(compute[w] for w in window)
                if gap_bytes >= nbytes and gap_bytes > 0:
                    lines.append(
                        f"{kind} {nbytes}B hidden (scheduled "
                        f"{first - index[name]} op(s) ahead of its "
                        f"first consumer; ~{gap_bytes}B independent "
                        "compute in the gap hides it)")
                    continue
        exposed += nbytes
        related = anc | desc
        indep = [c for c in compute if c != name and c not in related]
        indep_bytes = sum(compute[c] for c in indep)
        lines.append(
            f"{kind} {nbytes}B EXPOSED (synchronous); independent "
            f"compute available to hide it: {len(indep)} op(s) "
            f"~{indep_bytes}B results")
    return exposed, total, lines


@dataclasses.dataclass
class StepCostReport:
    """Structured per-step cost/memory ledger of one compiled program."""
    flops: float = 0.0               # per-device-program FLOPs per step
    bytes_accessed: float = 0.0      # HBM traffic per step (per device)
    transcendentals: float = 0.0
    temp_bytes: int = 0              # peak scratch (activations live here)
    argument_bytes: int = 0
    output_bytes: int = 0
    alias_bytes: int = 0             # donated inputs aliased into outputs
    generated_code_bytes: int = 0
    collective_counts: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    collective_bytes: int = 0
    collective_lines: List[str] = dataclasses.field(default_factory=list)
    # network attribution (collective_axis_stats): every collective's
    # bytes split by the fabric its replica groups span — intra-slice
    # ICI vs the inter-slice DCN link. On a single-slice mesh
    # dcn_bytes == 0 by construction; on a hybrid mesh dcn_bytes is THE
    # budgeted number DCN_SYNC=hier shrinks by 1/ici_size.
    ici_bytes: int = 0
    dcn_bytes: int = 0
    dcn_lines: List[str] = dataclasses.field(default_factory=list)
    # overlap/exposure ledger (overlap_stats): collective bytes the
    # schedule leaves EXPOSED (no compute hides their latency), the
    # hidden fraction, and the per-collective attribution lines — the
    # budget fields ROADMAP #3's overlap work moves
    exposed_collective_bytes: int = 0
    overlap_frac: float = 1.0
    exposure_lines: List[str] = dataclasses.field(default_factory=list)
    n_devices: int = 1
    tokens_per_step: Optional[int] = None

    # -- derived ------------------------------------------------------
    def flops_per_token(self) -> Optional[float]:
        if not self.tokens_per_step:
            return None
        # report flops are per device; tokens_per_step is global
        return self.flops * self.n_devices / self.tokens_per_step

    def ceilings(self, chip: Optional[ChipSpec] = None) -> Dict[str, float]:
        """Roofline at ``chip`` (default: the attached device kind):
        step-time lower bounds from compute, HBM traffic, and the
        network (EXPOSED collective bytes over the fabric they span —
        ICI intra-slice, DCN across; hidden bytes overlap compute by
        definition and never bound the step), and the MFU ceiling the
        binding term implies. An asserted *analytic* bound — measured
        MFU can only be below it."""
        chip = chip or chip_spec_for_devices()
        t_compute = self.flops / chip.peak_flops
        t_hbm = self.bytes_accessed / chip.hbm_bytes_per_s
        # exposed bytes split by fabric in the same dcn:ici proportion
        # as the total traffic (the schedule does not tag exposure per
        # fabric); with no attribution recorded everything rides ICI
        total_coll = max(self.collective_bytes, 1)
        exp_dcn = self.exposed_collective_bytes * self.dcn_bytes \
            / total_coll
        exp_ici = self.exposed_collective_bytes - exp_dcn
        t_ici = exp_ici / chip.ici_bytes_per_s
        t_dcn = exp_dcn / chip.dcn_bytes_per_s
        bound = max(t_compute, t_hbm, t_ici + t_dcn, 1e-30)
        return {
            "chip": chip.name,
            "compute_bound_step_s": t_compute,
            "hbm_bound_step_s": t_hbm,
            "ici_bound_step_s": t_ici,
            "dcn_bound_step_s": t_dcn,
            "mfu_ceiling": t_compute / bound,
        }

    def to_dict(self, *, include_lines: bool = True) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        if not include_lines:
            d.pop("collective_lines")
            d.pop("exposure_lines")
            d.pop("dcn_lines")
        return d

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "StepCostReport":
        known = {f.name for f in dataclasses.fields(StepCostReport)}
        return StepCostReport(**{k: v for k, v in d.items() if k in known})

    def summary(self) -> Dict[str, Any]:
        """Compact form for one-line JSON records (the autotune and
        analysis CLIs print it)."""
        out = {
            "flops_per_step": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "alias_bytes": self.alias_bytes,
            "collectives": {k: v for k, v in self.collective_counts.items()
                            if v},
            "collective_bytes": self.collective_bytes,
            "ici_bytes": self.ici_bytes,
            "dcn_bytes": self.dcn_bytes,
            "exposed_collective_bytes": self.exposed_collective_bytes,
            "overlap_frac": self.overlap_frac,
        }
        fpt = self.flops_per_token()
        if fpt is not None:
            out["flops_per_token"] = round(fpt, 1)
        out.update({k: v for k, v in self.ceilings().items()
                    if k in ("chip", "mfu_ceiling")})
        return out


def step_cost_report(compiled, *, tokens_per_step: Optional[int] = None,
                     num_slices: Optional[int] = None) -> StepCostReport:
    """Build a :class:`StepCostReport` from ``jit(...).lower(...)
    .compile()`` output. Works with no accelerator attached — every
    number comes from XLA's compile-time analyses.

    ``num_slices``: the DCN topology the program's collectives are
    attributed against (``ici_bytes``/``dcn_bytes``; default: the
    ``slice_assignments`` contract — real devices' ``.slice_index``,
    else ``$NUM_SLICES``, else one slice = everything ICI)."""
    report = StepCostReport(n_devices=max(len(jax.devices()), 1),
                            tokens_per_step=tokens_per_step)
    ca = compiled.cost_analysis()
    if ca:
        report.flops = float(ca.get("flops", 0.0))
        report.bytes_accessed = float(ca.get("bytes accessed", 0.0))
        report.transcendentals = float(ca.get("transcendentals", 0.0))
    ma = compiled.memory_analysis()
    if ma is not None:
        report.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
        report.argument_bytes = int(getattr(ma, "argument_size_in_bytes", 0))
        report.output_bytes = int(getattr(ma, "output_size_in_bytes", 0))
        report.alias_bytes = int(getattr(ma, "alias_size_in_bytes", 0))
        report.generated_code_bytes = int(
            getattr(ma, "generated_code_size_in_bytes", 0))
    try:
        hlo = compiled.as_text()
    except Exception:  # noqa: BLE001 - some backends cannot re-text
        hlo = ""
    # one parse of the (potentially multi-MB) HLO text, shared by the
    # three collective analyses
    comps = _computation_lines(hlo)
    trips = _while_trip_counts(hlo, comps)
    counts, cbytes, lines = collective_stats(hlo, _comps=comps,
                                             _trips=trips)
    report.collective_counts = counts
    report.collective_bytes = cbytes
    report.collective_lines = lines
    from gke_ray_train_tpu.parallel.mesh import slice_assignments
    slice_map = slice_assignments(jax.devices(), num_slices)
    ici, dcn, dcn_lines = collective_axis_stats(hlo, slice_map,
                                                _comps=comps,
                                                _trips=trips)
    report.ici_bytes = ici
    report.dcn_bytes = dcn
    report.dcn_lines = dcn_lines
    exposed, frac, exp_lines = overlap_stats(hlo, _trips=trips)
    report.exposed_collective_bytes = exposed
    report.overlap_frac = frac
    report.exposure_lines = exp_lines
    return report


def assert_state_donation(compiled, state: Any,
                          *, min_frac: float = 0.8) -> int:
    """Assert the train-state donation actually held: the aliased bytes
    XLA reports must cover ≥ ``min_frac`` of the state's own bytes
    (params + optimizer state alias into their updated outputs — the
    memory-headroom contract ``donate_argnums=(0, ...)`` exists for).
    Returns the aliased byte count. Donated *batch* buffers have no
    matching output, so they are invisible to ``memory_analysis`` —
    their freeing is asserted structurally (``donate_argnums``), not
    here."""
    ma = compiled.memory_analysis()
    if ma is None:  # pragma: no cover - backend without the analysis
        return -1
    from gke_ray_train_tpu.ops.quant import stored_bits
    # bits, not itemsize: NF4 codes are two a byte on the device
    state_bytes = sum(
        x.size * stored_bits(x.dtype) // 8 for x in jax.tree.leaves(state)
        if hasattr(x, "dtype")) // max(len(jax.devices()), 1)
    alias = int(ma.alias_size_in_bytes)
    if alias < min_frac * state_bytes:
        raise AssertionError(
            f"state donation did not hold: {alias} aliased bytes vs "
            f"~{state_bytes} per-device state bytes (donated buffers "
            "not reused — check donate_argnums and output layout)")
    return alias
