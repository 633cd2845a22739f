"""shardlint level 2 — trace/compile-level analyzers (no accelerator).

Everything here runs on the 8-fake-device CPU mesh CI uses (the same
re-exec recipe as ``perf.budget``): the presets' real step functions go
through ``jit(...).lower(...).compile()`` and three properties are
asserted off XLA's own compile-time ledger:

- **No unintended reshard**: collectives in the optimized HLO beyond
  the counts the checked-in budget (``tests/budgets/*.json``) allows —
  an extra all-gather in the grad path is the GSPMD signature of a
  ``PartitionSpec`` typo silently replicating an operand. Composes
  with ``perf/budget.py`` (the budget is the "intended collective
  set") instead of duplicating its comparator.
- **Donation actually held**: ``memory_analysis`` alias bytes must
  cover the state (``perf.costs.assert_state_donation``); when XLA
  drops a donation the finding names the alias shortfall and the
  aliasing the module header DID keep.
- **Compile-once**: :class:`RecompileDetector` counts compiles per
  function (a ``jax.monitoring`` hook counts backend compiles; the
  ``jax_log_compiles`` stream supplies the per-function signature) and
  reports any function compiled more than once WITH the
  shape/dtype/sharding diff that caused it.
"""

from __future__ import annotations

import difflib
import logging
import re
from typing import Any, Callable, Dict, List, Optional

logger = logging.getLogger(__name__)

# the jax_log_compiles line pxla emits per compile (jax 0.9):
#   Compiling jit(<name>) with global shapes and types
#   (ShapedArray(...),). Argument mapping: (<shardings>).
_COMPILE_LOG_RE = re.compile(
    r"Compiling jit\(([^\s]+)\) with global shapes and types "
    r"(\(.*?\))\. Argument mapping: (\(.*\))", re.DOTALL)
_PXLA_LOGGER = "jax._src.interpreters.pxla"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_PRIMITIVE_NAMES: Optional[frozenset] = None


def _primitive_names() -> frozenset:
    """Names of jax's registered primitives. The apply-primitive path
    wraps single ops in jits NAMED AFTER the primitive
    (``broadcast_in_dim``, ``convert_element_type``, ...) and recompiles
    them per static shape with an identical-looking signature — op-level
    noise, not the step-fn churn the detector exists for, and it must
    never trip the RECOMPILE_LIMIT hard error."""
    global _PRIMITIVE_NAMES
    if _PRIMITIVE_NAMES is None:
        names = set()
        try:
            from jax._src.interpreters import mlir
            names = {p.name for p in mlir._lowerings}
        except Exception as e:  # noqa: BLE001 - private API drift
            logger.warning("primitive registry unavailable (%s); "
                           "op-level compile noise may be attributed "
                           "to user functions", e)
        _PRIMITIVE_NAMES = frozenset(names)
    return _PRIMITIVE_NAMES


class RecompileDetector:
    """Counts compiles per function signature while active.

    Two sources, cross-checked: a ``jax.monitoring`` duration hook
    counts every backend compile (no names attached), and the
    ``jax_log_compiles`` log stream attributes each compile to a
    function name + abstract signature + sharding mapping. ``report()``
    returns every function compiled more than once, with the diff
    between consecutive signatures — the shape/dtype/sharding churn
    that caused the retrace.

    ``on_compile_over``: callback fired (name, signatures) the moment
    one function exceeds ``over_count`` compiles — the hard-error hook
    ``analysis.guards.install_recompile_limit`` uses. Exceptions it
    raises propagate out of the offending compile call by design.

    Caveat: attribution rides the log stream, so a global
    ``logging.disable(WARNING)`` (or raising the pxla logger past
    WARNING) blinds the detector — the backend-compile monitoring
    counter still ticks, the per-function table does not.
    """

    def __init__(self, on_compile_over: Optional[Callable] = None,
                 over_count: Optional[int] = None):
        self.compiles: Dict[str, List[str]] = {}
        self.backend_compiles = 0
        self._on_over = on_compile_over
        self._over = over_count
        self._handler: Optional[logging.Handler] = None
        self._prev_flag: Optional[bool] = None
        self._dur_listener = None

    # -- lifecycle ----------------------------------------------------
    def start(self) -> "RecompileDetector":
        import jax
        detector = self

        class _Handler(logging.Handler):
            def emit(self, record):
                detector._on_log(record.getMessage())

        self._handler = _Handler(level=logging.DEBUG)
        self._prev_flag = bool(jax.config.jax_log_compiles)
        jax.config.update("jax_log_compiles", True)
        logging.getLogger(_PXLA_LOGGER).addHandler(self._handler)
        try:
            from jax._src import monitoring

            def on_duration(event, duration, **kw):
                if event == _BACKEND_COMPILE_EVENT:
                    detector.backend_compiles += 1

            self._dur_listener = on_duration
            monitoring.register_event_duration_secs_listener(on_duration)
        except Exception as e:  # noqa: BLE001 - counters stay log-only
            logger.warning("jax.monitoring unavailable (%s); backend "
                           "compile counter disabled", e)
        return self

    def stop(self) -> None:
        import jax
        if self._handler is not None:
            logging.getLogger(_PXLA_LOGGER).removeHandler(self._handler)
            self._handler = None
        if self._prev_flag is not None:
            jax.config.update("jax_log_compiles", self._prev_flag)
            self._prev_flag = None
        if self._dur_listener is not None:
            try:
                from jax._src import monitoring
                monitoring._unregister_event_duration_listener_by_callback(
                    self._dur_listener)
            except Exception:  # noqa: BLE001 - private API; leak one noop
                pass
            self._dur_listener = None

    def __enter__(self) -> "RecompileDetector":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- accounting ---------------------------------------------------
    def _on_log(self, message: str) -> None:
        m = _COMPILE_LOG_RE.search(message)
        if not m:
            return
        name, avals, mapping = m.groups()
        if name in _primitive_names():
            return
        sigs = self.compiles.setdefault(name, [])
        sigs.append(f"shapes {avals} shardings {mapping}")
        if self._on_over is not None and self._over is not None \
                and len(sigs) > self._over:
            self._on_over(name, list(sigs))

    def recompiled(self) -> Dict[str, List[str]]:
        """name -> signatures, for every fn compiled more than once."""
        return {k: v for k, v in self.compiles.items() if len(v) > 1}

    @staticmethod
    def describe_churn(sigs: List[str], cap: int = 12) -> str:
        """Unified diff between consecutive signatures — the concrete
        shape/dtype/sharding change that caused each retrace."""
        out: List[str] = []
        for i in range(1, len(sigs)):
            if sigs[i - 1] == sigs[i]:
                out.append(f"compile {i} -> {i + 1}: identical visible "
                           "signature (static-arg or weak-type churn, "
                           "or a trace-cache miss)")
                continue
            delta = [ln for ln in difflib.ndiff([sigs[i - 1]], [sigs[i]])
                     if ln[:1] in "+-?"]
            out.append(f"compile {i} -> {i + 1}:")
            out.extend("  " + ln for ln in delta[:cap])
        return "\n".join(out)

    def findings(self) -> List[str]:
        out = []
        for name, sigs in sorted(self.recompiled().items()):
            out.append(
                f"{name!r} compiled {len(sigs)} times — a step fn must "
                "compile once; signature churn:\n"
                + self.describe_churn(sigs))
        return out


# ---------------------------------------------------------------------------
# collective / donation analyzers (compose with perf.budget's ledger)
# ---------------------------------------------------------------------------

def unbudgeted_collectives(report: Any, budget: Dict[str, Any]) -> List[str]:
    """Collectives beyond what the checked-in budget sanctions. One-
    sided by design: EXTRA collectives are the reshard/replication
    signal; "fewer than budget" is the budget comparator's own
    (two-sided) business."""
    from gke_ray_train_tpu.perf.budget import _hlo_delta
    from gke_ray_train_tpu.perf.costs import COLLECTIVE_KINDS
    if hasattr(report, "to_dict"):
        report = report.to_dict()
    want = budget.get("collective_counts") or {}
    have = report.get("collective_counts") or {}
    out: List[str] = []
    extra = [k for k in COLLECTIVE_KINDS
             if int(have.get(k, 0)) > int(want.get(k, 0))]
    if extra:
        detail = ", ".join(f"{k}: {have.get(k, 0)} vs budgeted "
                           f"{want.get(k, 0)}" for k in extra)
        lines = _hlo_delta(report.get("collective_lines", []),
                           budget.get("collective_lines", []))
        out.append(
            f"collectives beyond the budgeted set ({detail}) — an "
            "unbudgeted all-gather/all-reduce usually means a sharded "
            "operand is being RESHARDED to replicated (PartitionSpec "
            "typo or missing constraint)\n" + "\n".join(lines))
    return out


def unbudgeted_dcn_bytes(report: Any, budget: Dict[str, Any],
                         *, headroom: float = 0.10) -> List[str]:
    """Cross-slice (DCN) bytes beyond what the checked-in budget
    sanctions. One-sided like :func:`unbudgeted_collectives` — EXTRA
    bytes over the slow inter-slice link are the reshard signal (a
    PartitionSpec change that re-replicates an operand silently turns
    an intra-slice gather into a slice-spanning one); *fewer* DCN
    bytes is the two-sided comparator's business. The finding carries
    the per-op slice-crossing delta so the fattened hop is named."""
    from gke_ray_train_tpu.perf.budget import _hlo_delta
    if hasattr(report, "to_dict"):
        report = report.to_dict()
    want = budget.get("dcn_bytes")
    if want is None:        # pre-DCN budget: nothing to gate against
        return []
    have = int(report.get("dcn_bytes", 0))
    if have <= int(want) * (1.0 + headroom):
        return []
    lines = _hlo_delta(report.get("dcn_lines", []),
                       budget.get("dcn_lines", []))
    return [
        f"cross-slice DCN bytes beyond the budgeted set ({have} vs "
        f"budgeted {want}, headroom {headroom:.0%}) — a reshard is "
        "fattening the slice-spanning hop (full-payload traffic the "
        "hierarchical sync exists to avoid crossing DCN)\n"
        + "\n".join(lines)]


def donation_findings(compiled, state: Any, *, min_frac: float = 0.8,
                      label: str = "train_step") -> List[str]:
    """Did the declared donation actually hold? ``memory_analysis``
    alias bytes must cover ≥ min_frac of the per-device state bytes;
    a shortfall names the gap (XLA drops donations whose layouts or
    liveness don't line up — silently, unless asked to warn)."""
    from gke_ray_train_tpu.perf.costs import assert_state_donation
    try:
        assert_state_donation(compiled, state, min_frac=min_frac)
        return []
    except AssertionError as e:
        kept = "none"
        try:
            header = compiled.as_text().splitlines()[0]
            m = re.search(r"input_output_alias=\{([^}]*(?:\{[^}]*\}[^}]*)*)\}",
                          header)
            if m:
                kept = f"only {m.group(1).count('(')} aliased buffers"
        except Exception:  # noqa: BLE001 - diagnostics are best-effort
            pass
        return [f"{label}: {e} (module header kept {kept})"]


# ---------------------------------------------------------------------------
# preset-level check/trace (the CLI's `check` and `trace` verbs)
# ---------------------------------------------------------------------------

def check_serve_preset(name: str, *, budget_dir: Optional[str] = None
                       ) -> List[str]:
    """Level-2 findings for a serving-decode preset (serve/engine.py):
    the decode step must stay within its checked-in budget (any
    collective showing up in the mesh-local decode is a reshard bug),
    its KV-pool donation must hold, and a second same-signature decode
    dispatch must be a trace-cache hit — the continuous-batching loop
    runs it thousands of times per request stream."""
    import os

    import jax

    from gke_ray_train_tpu.perf.budget import (
        budget_path, build_serve_preset_step, load_budget)
    from gke_ray_train_tpu.perf.costs import step_cost_report

    findings: List[str] = []
    compiled, params, state, jitted, lora_arg = build_serve_preset_step(
        name, with_jitted=True)

    report = step_cost_report(compiled)
    bpath = budget_path(name, budget_dir)
    if os.path.exists(bpath):
        findings.extend(unbudgeted_collectives(report, load_budget(bpath)))
    else:
        logger.warning("no budget at %s; collective check skipped "
                       "(run: python -m gke_ray_train_tpu.perf.budget "
                       "record)", bpath)

    # the serve state (dominated by the [max_batch, bucket] KV pool) is
    # donated through every decode iteration — a dropped donation
    # doubles the pool's footprint at exactly max_batch scale
    findings.extend(donation_findings(compiled, state,
                                      label=f"{name} decode_step"))

    with RecompileDetector() as det:
        state1 = jax.block_until_ready(jitted(params, state, lora_arg))
        jax.block_until_ready(jitted(params, state1, lora_arg))
    findings.extend(det.findings())
    return [f"{name}: {f}" for f in findings]


def check_preset(name: str, *, budget_dir: Optional[str] = None
                 ) -> List[str]:
    """All level-2 findings for one perf.budget preset: unbudgeted
    collectives, dropped donation, and any recompile on a second
    same-signature step call. Serve presets route to
    :func:`check_serve_preset`."""
    import os

    import jax

    from gke_ray_train_tpu.perf.budget import (
        SERVE_PRESETS, budget_path, build_preset_step, load_budget)
    from gke_ray_train_tpu.perf.costs import step_cost_report

    if name in SERVE_PRESETS:
        return check_serve_preset(name, budget_dir=budget_dir)

    findings: List[str] = []

    # one undonated build serves BOTH the collective check (donate=False
    # matches the recorded budget baseline exactly) and the compile-once
    # probe below — a preset build is a full model+state construction
    # plus an XLA compile, not something to repeat for free
    compiled, state, batch, jitted = build_preset_step(name,
                                                       with_jitted=True)

    # 1) collectives vs the checked-in budget (the DCN attribution runs
    #    against the preset's declared slice layout) — plus the
    #    one-sided cross-slice byte rule: a reshard that fattens the
    #    DCN hop fails `analysis check` even inside the two-sided
    #    comparator's tolerance band
    from gke_ray_train_tpu.perf.budget import PRESETS
    preset = PRESETS[name]
    report = step_cost_report(compiled, num_slices=preset.num_slices)
    bpath = budget_path(name, budget_dir)
    if os.path.exists(bpath):
        budget = load_budget(bpath)
        findings.extend(unbudgeted_collectives(report, budget))
        findings.extend(unbudgeted_dcn_bytes(report, budget))
    else:
        logger.warning("no budget at %s; collective check skipped "
                       "(run: python -m gke_ray_train_tpu.perf.budget "
                       "record)", bpath)

    # 2) donation holds on the donated build
    donated, state_d, _ = build_preset_step(name, donate=True)
    findings.extend(donation_findings(donated, state_d,
                                      label=f"{name} train_step"))

    # 3) compile-once: dispatch the JITTED step twice with identical
    #    signatures — the second call must be a trace-cache hit
    #    (donate=False so the same placed batch is reusable)
    with RecompileDetector() as det:
        state1, _ = jax.block_until_ready(jitted(state, batch))
        jax.block_until_ready(jitted(state1, batch))
    findings.extend(det.findings())
    return [f"{name}: {f}" for f in findings]


def trace_preset(name: str) -> str:
    """Human-readable level-2 report for one preset (the CLI `trace`
    verb): the cost ledger + donation + collective census."""
    from gke_ray_train_tpu.perf.budget import (
        SERVE_PRESETS, build_preset_step, build_serve_preset_step)
    from gke_ray_train_tpu.perf.costs import step_cost_report

    if name in SERVE_PRESETS:
        compiled, _, state = build_serve_preset_step(name)
        label = "decode_step"
    else:
        compiled, state, _ = build_preset_step(name, donate=True)
        label = "train_step"
    report = step_cost_report(compiled)
    lines = [f"== {name} =="]
    for k, v in sorted(report.summary().items()):
        lines.append(f"  {k}: {v}")
    don = donation_findings(compiled, state, label=label)
    lines.append("  donation: " + (don[0] if don else "held"))
    for hlo in report.collective_lines:
        lines.append(f"  HLO {hlo}")
    return "\n".join(lines)
