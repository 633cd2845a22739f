"""Causal span tracing — the Dapper-style layer under the event stream
(ISSUE 14 tentpole, part 1).

PR 10's events say *that* a boundary happened; spans say how long it
took and what it was caused by. Every instrumented region — the worker
attempt and its ledger-timed children (restore / compile /
fast-forward / step windows / eval / checkpoint saves / the preemption
grace save), the elastic reshard (both the plan re-formation and the
resharded restore), and the serve engine's per-request lifecycle
(enqueue → prefill → decode iterations → retire) — lands as ONE JSON
line in ``<obs_dir>/spans-r<rank>.jsonl`` (driver: ``spans-rdriver``),
written when the span ENDS (complete-span records survive the SIGKILL
that usually follows the interesting ones; an in-flight span simply
never lands, which is itself a signal).

Identity is W3C-trace-context shaped:

- ``trace_id`` (32 hex) is derived DETERMINISTICALLY from the run id
  (``sha256(OBS_RUN_ID)``), so every rank of every attempt — including
  driverless multi-rank sessions that never exchange a parent — agrees
  on one trace without another env hop.
- ``span_id`` (16 hex) is random per span; the driver's per-attempt
  span id rides to workers as ``OBS_PARENT_SPAN`` through the same
  env-forwarding path as ``OBS_RUN_ID``/``OBS_ATTEMPT``, so the worker
  attempt spans parent under the driver attempt span and the merged
  DAG is connected across processes.

The span-name vocabulary is CLOSED like the event vocabulary:
:data:`SPAN_NAMES` is pinned by the shipped
``obs/schemas/trace.schema.json`` and enforced AT THE EMIT SITE — an
unknown name or stray attribute raises instead of silently orphaning
``obs/critical.py``'s term mapping.

Hot-path contract (the obs/ discipline): JSONL spans are emitted at
the boundaries the ledger already times, from host floats the caller
already measured — never per step (step windows aggregate at the log
cadence), never with a device fetch of their own. The loss stream with
TRACE=1 is asserted BITWISE-identical to obs-off.

The profiler's clock (ISSUE 24). :func:`region` is the ONE way a region
is opened: it always enters a ``jax.profiler.TraceAnnotation`` named
``grt:<name>`` (a flag check while no profiler runs), so whatever
window ``TraceProfiler`` / ``obs/capture.py`` / a benchmark opens holds
the program's phases on its host plane, and it stamps
``time.perf_counter()`` endpoints. A finished region goes to the
bounded in-memory :data:`RECORD` while a profiler is attached to the
loop or an obs session is active (per-step names live ONLY there), and
to the JSONL stream under the rule above. The device side of the same
window is named by :data:`SCOPE_NAMES` (``jax.named_scope`` in the
model and the step) and :data:`KERNEL_NAMES` (``pl.pallas_call(name=)``);
:func:`scope_table` reduces a compiled step's HLO text to
``{instruction name: op_name}`` so a device event can be joined to them.

Stdlib-only at import (the report/critical-path side runs with no
jax); :func:`region` and :func:`scope` import jax on first use.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import itertools
import json
import logging
import os
import re
import threading
import time
import uuid
from typing import (
    Any, Deque, Dict, Iterable, Iterator, List, Optional, Union)

logger = logging.getLogger(__name__)

# correlation fields stamped on EVERY span record, in this order.
# ``t0``/``t1`` are wall-clock (time.time) endpoints; ``dur_s`` is the
# authoritative duration — measured by the instrumented site itself
# (perf_counter spans / the exact float the goodput ledger booked), so
# ``obs/critical.py`` can reconcile spans against the ledger EXACTLY
# instead of within wall-clock re-derivation noise.
SPAN_STAMP = ("trace_id", "span_id", "parent_id", "name", "run_id",
              "attempt", "rank", "slice", "step", "t0", "t1", "dur_s")

# the closed span-name vocabulary: name -> allowed attribute fields.
# Pinned by obs/schemas/trace.schema.json + tests (both directions).
SPAN_NAMES: Dict[str, tuple] = {
    # run/attempt skeleton (driver writes `run` + one `attempt` per
    # attempt; every worker writes its own `attempt` span parented
    # under the driver's via OBS_PARENT_SPAN)
    "run": ("status",),
    "attempt": ("status",),
    # the ledger-timed loop boundaries (train/loop.py); durations are
    # the EXACT floats the GoodputLedger booked for the same regions
    "restore": ("resumed_step",),
    # the first step call of a run_training call and the wait for it:
    # once a call, so it is among ALWAYS_RECORDED
    "compile": (),
    "fast_forward": (),
    "step_window": ("steps", "data_stall_s"),
    "eval": (),
    "ckpt_save": ("forced",),
    # async-commit save twin of ckpt_save (ISSUE 18): the loop's
    # residual blocking window (snapshot + enqueue) — the exact float
    # booked as ckpt_async_s; the storage commit runs in a background
    # thread and is an EVENT (ckpt_commit), never a span, because it
    # occupies no loop wall-clock to attribute
    "ckpt_snapshot": ("forced",),
    # restore served from a peer slice's hot state (ckpt/peer.py) —
    # the exact float booked as peer_restore_s
    "peer_restore": ("resumed_step",),
    "preempt_save": (),
    # elastic reshard (rayint/elastic.py plan re-formation + the
    # ckpt/manager.py resharded restore — the same twin pair the
    # reshard EVENT merges; `where` tells them apart)
    "reshard": ("from_devices", "to_devices", "where"),
    # serve request lifecycle (serve/engine.py): one request span with
    # three children decomposing "where did my p99 go" — queue wait,
    # prefill, and the decode-iteration region it shared with the
    # continuous batch
    "serve_request": ("rid", "bucket", "prompt_len", "generated",
                      "finish_reason"),
    "serve_enqueue": ("rid",),
    "serve_prefill": ("rid",),
    "serve_decode": ("rid", "iterations"),
    # loop and build phases on the profiler's clock (:func:`region`).
    # One iteration of run_training's inner loop and its children, and
    # the prefetch thread's two stages: PER_STEP_SPANS, in-memory only
    "step_iter": (),
    "data_wait": (),
    "step_dispatch": (),
    "metrics_fetch": (),
    "log_emit": (),
    "batch_next": (),
    "batch_place": (),
    # perf/cache.py::build_or_load_step: a handful a process, recorded
    # always; `source` is "deserialized" | "compiled"; `remat_*`: what
    # the block checkpoints keep beside their inputs (train/remat.py);
    # `flash_grid`: grid steps a flash call visits against its
    # rectangular grid's, by attention kind and kernel
    # (models/transformer.py::flash_grids). `ssm_scan`: the geometry of
    # a state-space layer's scan for the step's rows (the form they
    # take, `impl`: pallas / xla; chunk, chunks a row, heads, head size,
    # state, groups, heads a grid step or block, grid steps of a kernel
    # call a row, layers; models/transformer.py::ssm_geometry), {}
    # without such layers. `moe_gather`: the routed layer's
    # gather-and-sum for the step's tokens (the form, `impl`: pallas /
    # xla; tokens a grid step, the pair buffer's rows, a row's bytes,
    # picks a token; ops/moe.py::gather_geometry), {} without a dropless
    # routed layer. `nf4_matmul`: the frozen products for the step's
    # rows, by weight shape the form ops/quant.py::nf4_matmul_plan
    # picked, its tiles and the calls a micro-pass, then the calls of
    # each form (ops/quant.py::nf4_geometry), {} without a quantized
    # projection. `remat_estimate_bytes`: the peak the
    # chooser's arithmetic expects for the step it asked for (None
    # where no limit is reported). `xla_memory`: what XLA laid out for
    # the executable that will run, from `compiled.memory_analysis()`,
    # in bytes: peak, arguments, outputs, aliased, temporaries, code,
    # and `limit`, the device's own (None where it reports none); {}
    # where the backend gives no analysis
    "step_build": ("source", "remat_keep", "remat_keep_bytes",
                   "remat_budget_bytes", "remat_args_bytes",
                   "remat_keep_fallback", "flash_grid", "ssm_scan",
                   "moe_gather", "nf4_matmul", "remat_estimate_bytes",
                   "xla_memory"),
    # what jax's own events said while the region was open
    # (perf/cache.py's listener): `trace_s` the step's trace to a
    # jaxpr, `to_mlir_s` its lowering to a module, each the time of the
    # outermost events only (a jitted function traced inside the step
    # reports a duration of its own, which the step's holds already)
    "step_lower": ("trace_s", "to_mlir_s"),
    # `cache`: "hit" (the executable came out of the persistent compile
    # cache) | "miss" (it was built and written there) | None (no
    # cache in use); `retrieval_s`: the read; `backend_compile_s`: the
    # build, 0 on a hit. `step_build`'s `source` says another thing:
    # whether a sidecar was deserialized, with no compile at all
    "step_compile": ("cache", "retrieval_s", "backend_compile_s"),
    # set-up outside the build, once a call each, recorded always:
    # train/step.py::make_train_state (`args_bytes`: one device's share
    # of the state it made) and the whole of one run_training call,
    # entry to return, the parent of what the loop opens (`steps`
    # trained in the call; `to_first_step_s`: the loop's own
    # restart_to_first_step_s). obs/critical.py books neither to a
    # ledger term: what they hold is booked by their children
    "state_build": ("args_bytes",),
    "train_loop": ("steps", "to_first_step_s"),
}

# names that never reach the JSONL stream: they end once a step (or
# once a batch on the prefetch thread), and the stream's contract is
# the log cadence
PER_STEP_SPANS = frozenset({
    "step_iter", "data_wait", "step_dispatch", "metrics_fetch",
    "log_emit", "batch_next", "batch_place"})
# names recorded in memory whether or not anything listens: outside
# every hot path (they end once a build or once a run_training call),
# and the set-up they time is over before a profiler could be attached
ALWAYS_RECORDED = frozenset({"step_build", "step_lower", "step_compile",
                             "state_build", "train_loop", "compile"})

# the closed vocabulary of jax.named_scope names on the device ops of
# the train step (models/transformer.py, ops/moe.py, train/step.py,
# train/optim.py). A "/" is two nested scopes. `base` / `lora` are the
# leaf scopes inside `_proj`: the frozen (maybe dequantized) projection
# against the adapter bypass, whatever module calls it. `window` /
# `full` are leaf scopes inside `attn/core`, opened only by a model
# that has both kinds of layer, so that each kind's attention can be
# billed its own pairs. The `moe/` names are the routed layer's stages
# (ops/moe.py): scores and selection, the sort and gather into the pair
# buffer, the grouped products, the weighted way back, and the shared
# expert beside them. The phase costs no name: jax writes `jvp(`
# (forward), `transpose(` (backward) and `rematted_computation`
# (recomputed forward) into every op_name.
SCOPE_NAMES = (
    "embed", "attn_norm", "attn/qkv", "attn/qk_norm", "attn/rope",
    "attn/core", "attn/out", "mlp_norm", "mlp/gate_up", "mlp/down",
    "moe/route", "moe/dispatch", "moe/experts", "moe/combine",
    "moe/shared", "final_norm", "unembed", "loss", "clip", "optimizer",
    "base", "lora", "window", "full",
    # a latent-attention layer: each latent's down-projection, norm and
    # up-projection, and the leaf scope its attention runs under
    "attn/q_latent", "attn/kv_latent", "latent",
    # a state-space layer's mixer (models/transformer.py::_ssm): the
    # first projection, the causal conv with its SiLU, the selective
    # scan (ops/ssm.py), the gate with its norm, and the second
    # projection with the residual add
    "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm",
    "ssm/out_proj")

# bumped when a scope MOVES without the vocabulary changing: it rides
# the compile cache's key beside the names (perf/cache.py::names_salt),
# so that an executable compiled under the old placement is not served.
# 2: `moe/experts` no longer holds the routed layer's residual add
# 3: a latent-attention layer projects under `attn/q_latent` and
#    `attn/kv_latent`, and its `attn/rope` holds the assembly of q and k
# 4: a state-space layer's mixer runs under the `ssm/` names
SCOPE_VERSION = 4

# pl.pallas_call(name=...) of every kernel (ops/flash_attention.py,
# ops/fused_ce.py, ops/fused_norm_rope.py, ops/ssm.py, ops/moe.py,
# ops/quant.py), then the
# library's kernels the program calls under the names the library gave
# them: jax's megablox grouped matmul and its transpose (ops/moe.py)
LIBRARY_KERNEL_NAMES = ("gmm", "tgmm")
KERNEL_NAMES = (
    "flash_fwd", "flash_dq", "flash_dkv", "fused_ce_fwd", "fused_ce_dx",
    "fused_ce_dhead", "fused_rmsnorm", "fused_rope_qk",
    "fused_rmsnorm_rope", "ssd_fwd", "ssd_states", "ssd_bwd",
    "moe_gather_sum", "nf4_matmul", "nf4_matmul_dx",
    ) + LIBRARY_KERNEL_NAMES

# the profiler's host plane shows a region under this prefix
ANNOTATION_PREFIX = "grt:"


class SpanError(ValueError):
    """A span violated the pinned schema (unknown name / stray attr)."""


def validate_span(name: str, attrs: Dict[str, Any]) -> None:
    """Schema teeth at the emit site (the events.py discipline): the
    contract critical-path extraction relies on is enforced where the
    span is born, not discovered at read time."""
    allowed = SPAN_NAMES.get(name)
    if allowed is None:
        raise SpanError(f"unknown span name {name!r}; known: "
                        f"{sorted(SPAN_NAMES)}")
    # stamp-named attrs are NOT allowed through: emit writes attrs
    # after the stamp dict, so a payload named `attempt`/`run_id`
    # would silently clobber the correlation fields the report groups
    # on (the explicit emit params — step/span_id/parent_id/t1 — are
    # the only sanctioned way to set those)
    stray = sorted(set(attrs) - set(allowed))
    if stray:
        raise SpanError(f"span {name!r} does not declare attributes "
                        f"{stray} (allowed: {sorted(allowed)})")


def trace_id_for_run(run_id: str) -> str:
    """The run's trace id, derived (not minted): every process that
    knows ``OBS_RUN_ID`` computes the same 32-hex id, so driverless
    multi-rank sessions still merge to ONE trace."""
    return hashlib.sha256(
        ("grt-trace:" + str(run_id)).encode()).hexdigest()[:32]


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


class SpanLog:
    """Append-only JSONL span writer for one (rank, attempt) stream —
    the spans twin of ``events.EventLog`` (same append/flush-per-record
    semantics, same correlation stamps)."""

    def __init__(self, path: str, *, run_id: str, attempt: int,
                 rank: Union[int, str],
                 slice_index: Optional[int] = None):
        self.path = path
        self.run_id = str(run_id)
        self.trace_id = trace_id_for_run(run_id)
        self.attempt = int(attempt)
        self.rank = rank
        self.slice_index = slice_index
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")

    def emit(self, name: str, dur_s: float, *,
             t1: Optional[float] = None,
             span_id: Optional[str] = None,
             parent_id: Optional[str] = None,
             step: Optional[int] = None,
             **attrs: Any) -> Dict[str, Any]:
        """Record one FINISHED span. ``dur_s`` is the caller's own
        measurement (authoritative); ``t1`` anchors it on the wall
        clock (default: now) and ``t0`` is derived — callers never
        have to carry two clocks."""
        validate_span(name, attrs)
        t1 = time.time() if t1 is None else float(t1)
        dur = max(float(dur_s), 0.0)
        rec: Dict[str, Any] = {
            "trace_id": self.trace_id,
            "span_id": span_id or new_span_id(),
            "parent_id": parent_id,
            "name": name,
            "run_id": self.run_id,
            "attempt": self.attempt,
            "rank": self.rank,
            "slice": self.slice_index,
            "step": None if step is None else int(step),
            "t0": round(t1 - dur, 6),
            "t1": round(t1, 6),
            "dur_s": dur,
        }
        for k, v in attrs.items():
            if v is None or isinstance(v, (bool, int, float, str)):
                rec[k] = v
            else:
                rec[k] = repr(v)[:200]
        if self._f is not None and not self._f.closed:
            self._f.write(json.dumps(rec) + "\n")
            self._f.flush()
        return rec

    def close(self) -> None:
        try:
            if self._f is not None and not self._f.closed:
                self._f.close()
        except OSError:  # pragma: no cover - close is best-effort
            pass


def spans_path(obs_dir: str, rank: Union[int, str]) -> str:
    return os.path.join(obs_dir, f"spans-r{rank}.jsonl")


def iter_spans(obs_dir: str,
               names: Optional[Iterable[str]] = None
               ) -> Iterator[Dict[str, Any]]:
    """Every span record under ``obs_dir`` (all ranks + driver), sorted
    by start time. Corrupt lines are skipped with a warning, never
    fatal (the ``iter_events`` contract)."""
    want = set(names) if names is not None else None
    out: List[Dict[str, Any]] = []
    try:
        entries = sorted(os.listdir(obs_dir))
    except OSError:
        return iter(())
    for fname in entries:
        if not (fname.startswith("spans-") and fname.endswith(".jsonl")):
            continue
        path = os.path.join(obs_dir, fname)
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    logger.warning("%s:%d: skipping corrupt span line",
                                   path, i + 1)
                    continue
                if want is None or rec.get("name") in want:
                    out.append(rec)
    out.sort(key=lambda r: (r.get("t0", 0.0), str(r.get("rank"))))
    return iter(out)


def schema_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "schemas", "trace.schema.json")


def load_schema() -> Dict[str, Any]:
    with open(schema_path(), encoding="utf-8") as f:
        return json.load(f)


def check_schema() -> List[str]:
    """Shipped schema file <-> code contract, both directions (the
    events.check_schema shape the CI lint step and tests call)."""
    findings: List[str] = []
    try:
        doc = load_schema()
    except (OSError, ValueError) as e:
        return [f"trace schema unreadable: {type(e).__name__}: {e}"]
    if tuple(doc.get("stamp", ())) != SPAN_STAMP:
        findings.append(f"schema stamp {doc.get('stamp')} != code "
                        f"SPAN_STAMP {list(SPAN_STAMP)}")
    names = doc.get("names", {})
    if set(names) != set(SPAN_NAMES):
        findings.append(
            f"schema names {sorted(set(names) ^ set(SPAN_NAMES))} "
            "drifted from code SPAN_NAMES")
    for k in set(names) & set(SPAN_NAMES):
        if tuple(names[k]) != tuple(SPAN_NAMES[k]):
            findings.append(f"schema name {k!r} attrs {names[k]} != "
                            f"code {list(SPAN_NAMES[k])}")
    for key, code in (("scopes", SCOPE_NAMES), ("kernels", KERNEL_NAMES),
                      ("per_step", sorted(PER_STEP_SPANS))):
        if list(doc.get(key, ())) != list(code):
            findings.append(f"schema {key} {doc.get(key)} != code "
                            f"{list(code)}")
    return findings


# ---------------------------------------------------------------------------
# regions: the profiler's host plane + the in-memory record
# ---------------------------------------------------------------------------

class Region:
    """One open region. ``dur_s`` (settable inside the block) is what
    the JSONL span carries — the ledger-timed sites set it to the exact
    float the GoodputLedger booked; unset, it is ``t1 - t0``. ``step``
    and ``attrs`` may be filled in while the region is open; ``drop``
    discards it (an iteration that found the stream exhausted)."""

    __slots__ = ("name", "id", "parent", "up", "t0", "t1", "step",
                 "attrs", "dur_s", "drop")

    def __init__(self, name: str, step: Optional[int],
                 attrs: Dict[str, Any], up: Optional["Region"]):
        self.name, self.step, self.attrs = name, step, attrs
        self.id = next(_ids)
        self.up = up                 # the region open around this one
        self.parent = None if up is None else up.id
        self.t0 = self.t1 = 0.0
        self.dur_s: Optional[float] = None
        self.drop = False


class MemoryRecord:
    """Finished regions kept in memory ("keep spans in memory and write
    them out when the benchmark ends"): bounded, appended to from the
    loop's thread and the prefetch threads (a deque append is atomic),
    read after the loop. ``attach`` / ``detach`` bracket a
    ``run_training`` call that was given a profiler object. A span is
    ``{"name", "id", "parent", "t0", "t1", "step", **attrs}`` with
    ``time.perf_counter()`` endpoints and the id of the region that was
    open on the same thread when it opened (while nothing listens, of
    the nearest such region that is itself recorded)."""

    def __init__(self, maxlen: int = 65536):
        self.spans: Deque[Dict[str, Any]] = collections.deque(
            maxlen=maxlen)
        # {step label: {instruction name: op_name}} (:func:`scope_table`)
        self.scope_tables: Dict[str, Dict[str, str]] = {}
        self.scope_table_s: Dict[str, float] = {}
        self._attached = 0

    def attach(self) -> None:
        self._attached += 1

    def detach(self) -> None:
        self._attached = max(self._attached - 1, 0)

    @property
    def attached(self) -> bool:
        return self._attached > 0

    def clear(self) -> None:
        self.spans.clear()
        self.scope_tables.clear()
        self.scope_table_s.clear()


# one per process, like the profiler it mirrors
RECORD = MemoryRecord()

_ids = itertools.count(1)
_open = threading.local()        # .stack: this thread's open regions
_annotation = None               # jax.profiler.TraceAnnotation, lazily
_runtime = None                  # obs.runtime (it imports this module)


@contextlib.contextmanager
def region(name: str, *, step: Optional[int] = None,
           **attrs: Any) -> Iterator[Region]:
    """Open one region of the closed vocabulary; the only way one is
    opened. With no profiler running, none attached and no session, it
    costs the annotation's flag check and one branch."""
    global _annotation, _runtime
    validate_span(name, attrs)
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        from gke_ray_train_tpu.obs import runtime
        _annotation, _runtime = TraceAnnotation, runtime
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    r = Region(name, step, attrs, stack[-1] if stack else None)
    stack.append(r)
    r.t0 = time.perf_counter()
    try:
        with _annotation(ANNOTATION_PREFIX + name):
            yield r
    finally:
        r.t1 = time.perf_counter()
        stack.pop()
        run = _runtime.active()
        if not r.drop and (run is not None or RECORD.attached
                           or name in ALWAYS_RECORDED):
            _finish(r, run)


def _finish(r: Region, run) -> None:
    # again: the name may have changed and attributes been added since
    validate_span(r.name, r.attrs)
    parent = r.parent
    if run is None and not RECORD.attached:
        # only the always-recorded names are kept now: the parent is
        # the nearest of them, so that a reader finds it in the record
        # (`compile` under `train_loop`, past the iteration's region)
        up = r.up
        while up is not None and up.name not in ALWAYS_RECORDED:
            up = up.up
        parent = None if up is None else up.id
    RECORD.spans.append({"name": r.name, "id": r.id, "parent": parent,
                         "t0": r.t0, "t1": r.t1, "step": r.step,
                         **r.attrs})
    if run is not None and r.name not in PER_STEP_SPANS:
        run.span_add(r.name,
                     r.t1 - r.t0 if r.dur_s is None else r.dur_s,
                     step=r.step, **r.attrs)


# ---------------------------------------------------------------------------
# scopes: the device side of the same window
# ---------------------------------------------------------------------------

def scope(name: str):
    """``jax.named_scope`` for one name of :data:`SCOPE_NAMES`."""
    if name not in SCOPE_NAMES:
        raise SpanError(f"unknown scope name {name!r}; known: "
                        f"{list(SCOPE_NAMES)}")
    import jax
    return jax.named_scope(name)


_TRANSFORM = re.compile(r"\w+\(|\)")


def scope_path(op_name: str) -> Optional[str]:
    """The vocabulary names an ``op_name`` lies under, outermost first
    and joined by ``/`` (``attn/qkv/base``), or None when it holds
    none. A name counts only as whole path segments; jax wraps the
    scopes that were open where a transform was applied into it
    (``transpose(jvp(loss))``), so the wrappers are peeled first."""
    segs = [x for x in _TRANSFORM.sub("", op_name).split("/") if x]
    found = []
    i = 0
    while i < len(segs):
        two = "/".join(segs[i:i + 2])
        if two in SCOPE_NAMES:
            found.append(two)
            i += 2
        else:
            if segs[i] in SCOPE_NAMES:
                found.append(segs[i])
            i += 1
    return "/".join(found) or None


_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_MATMUL = re.compile(r" (?:convolution|dot)\(")


def scope_table(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of a compiled executable's
    optimised HLO text (``compiled.as_text()``), for every instruction
    outside a fused computation — the names a device event carries. A
    fusion takes the ``op_name`` of the matmul it holds (``dot`` /
    ``convolution``) when it holds one, else its own (its root's): the
    frozen projection fused with the adapter's add is the projection."""
    own: Dict[str, str] = {}         # instruction -> its own op_name
    calls: Dict[str, str] = {}       # fusion -> fused computation
    matmul: Dict[str, str] = {}      # computation -> its first matmul's
    fused: set = set()               # instructions of fused computations
    comp = None
    members: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        if comp is None or not line.startswith(" "):
            m = _HLO_COMPUTATION.match(line)
            if m:
                comp = m.group(1)
                members[comp] = []
                continue
        m = _HLO_INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        members[comp].append(name)
        op = _HLO_OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        if op and comp not in matmul and _HLO_MATMUL.search(line):
            matmul[comp] = op.group(1)
        c = _HLO_CALLS.search(line)
        if c and " fusion(" in line:
            calls[name] = c.group(1)
    for target in calls.values():
        fused.update(members.get(target, ()))
    return {name: matmul.get(calls.get(name, ""), op) or op
            for name, op in own.items() if name not in fused}


def note_scope_table(label: str, compiled) -> Optional[Dict[str, str]]:
    """Reduce ``compiled`` (anything with ``as_text()``) to its scope
    table and keep it, with the seconds it took, in :data:`RECORD`
    under the step's label. None for an executable that cannot print
    itself (a deserialized one may not)."""
    t0 = time.perf_counter()
    try:
        text = compiled.as_text()
    except Exception as e:  # noqa: BLE001 - the profile survives without
        logger.warning("scope table of %s skipped: %s: %s", label,
                       type(e).__name__, e)
        return None
    table = scope_table(text)
    RECORD.scope_tables[label] = table
    RECORD.scope_table_s[label] = time.perf_counter() - t0
    logger.info("%s: scope table of %d instructions in %.2fs", label,
                len(table), RECORD.scope_table_s[label])
    return table
