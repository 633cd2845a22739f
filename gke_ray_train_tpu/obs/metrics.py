"""Cross-rank metrics registry + exporters (ISSUE 11 tentpole, part 2).

A lightweight counters/gauges/histograms registry every rank exports as
both a Prometheus textfile (``metrics-r<rank>.prom`` — a GKE-side
node-exporter textfile collector or sidecar scrapes it with NO new
deps) and JSON (``metrics-r<rank>.json`` — what ``obs report`` merges).

The metric NAME vocabulary is closed, like the event vocabulary:
:data:`METRIC_NAMES` is pinned by ``obs/schemas/metrics.schema.json``
and the test_obs contract test, so a renamed metric fails lint instead
of silently forking dashboards. The registry itself is dumb on purpose:
values are pushed by the code that already computed them (the loop's
log-cadence metrics, the goodput ledger at attempt close, the serve
engine's stats, the persistent-cache counters) — there is no second
computation path to drift.

Hot-path contract: ``Counter.inc``/``Gauge.set``/``Histogram.observe``
are a few python ops on host floats. Nothing here touches jax or the
device; ``pull_jax_counters`` reads the already-maintained host-side
``perf.cache`` counters. Stdlib-only.
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
from typing import Any, Dict, List, Optional

logger = logging.getLogger(__name__)

# the closed metric vocabulary: name -> type. Pinned by
# obs/schemas/metrics.schema.json + tests/test_obs.py. goodput_* mirror
# train/metrics.py LEDGER_TERMS via ledger_metrics() — one source.
METRIC_NAMES: Dict[str, str] = {
    # loop progress / throughput (train/loop.py log cadence)
    "steps_total": "counter",
    "loss": "gauge",
    "learning_rate": "gauge",
    "grad_norm": "gauge",
    "eval_loss": "gauge",
    "tokens_per_sec_per_chip": "gauge",
    "mfu": "gauge",
    "data_stall_frac": "gauge",
    # per-step host timing distributions (obs/capture.py feeds these —
    # host iteration wall, data wait; no device sync involved)
    "step_time_s": "histogram",
    "data_wait_s": "histogram",
    # goodput ledger terms (train/metrics.py LEDGER_TERMS + wall/frac)
    "goodput_compile_s": "gauge",
    "goodput_restore_s": "gauge",
    "goodput_fast_forward_s": "gauge",
    "goodput_data_stall_s": "gauge",
    "goodput_eval_ckpt_stall_s": "gauge",
    "goodput_ckpt_async_s": "gauge",
    "goodput_peer_restore_s": "gauge",
    "goodput_step_s": "gauge",
    "goodput_lost_s": "gauge",
    "goodput_wall_s": "gauge",
    "goodput_frac": "gauge",
    # network traffic of the compiled train step (perf/costs.py
    # StepCostReport, noted once at AOT build time by perf/cache.py —
    # no second computation): collective bytes split by the fabric
    # their replica groups span. grt_dcn_bytes is the cross-slice
    # number DCN_SYNC=hier shrinks; flat-lined at 0 on single-slice
    # pools by construction.
    "ici_bytes": "gauge",
    "dcn_bytes": "gauge",
    # compile-once health (perf/cache.py jax.monitoring counters)
    "compile_cache_hits": "gauge",
    "compile_cache_misses": "gauge",
    "compile_time_saved_s": "gauge",
    "backend_compiles_total": "counter",
    # anomaly-triggered profiling (obs/capture.py)
    "anomalies_total": "counter",
    "captures_total": "counter",
    # serving (serve/engine.py stats(); tests/test_obs.py pins the two equal)
    "serve_iterations_total": "counter",
    "serve_refills_total": "counter",
    "serve_completed_total": "counter",
    "serve_p50_token_latency_s": "gauge",
    "serve_p99_token_latency_s": "gauge",
    "serve_batch_occupancy": "gauge",
    # multi-tenant serving (ISSUE 17): adapter-pool residency churn
    # (serve/adapters.py LRU), host-side prefix/KV reuse, and the
    # speculative-decode acceptance ledger (proposed draft tokens vs
    # target-verified accepts — the throughput lever's own telemetry)
    "serve_adapter_hits_total": "counter",
    "serve_adapter_misses_total": "counter",
    "serve_adapter_evictions_total": "counter",
    "serve_prefix_hits_total": "counter",
    "serve_spec_proposed_total": "counter",
    "serve_spec_accepted_total": "counter",
    # admitted request length (prompt + max_new_tokens) at the engine's
    # submit path — the workload-shape distribution bucket-padding and
    # MAX_BATCH tuning decisions are made against
    "request_len": "histogram",
}

PROM_PREFIX = "grt_"      # gke_ray_train_tpu, short for scrape configs


class MetricError(ValueError):
    """A metric violated the pinned name/type vocabulary."""


class Counter:
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


class Gauge:
    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, value: float) -> None:
        self.value = float(value)


class Histogram:
    """count/sum plus a bounded RESERVOIR sample for p50/p99 — enough
    for the serving-latency shape without a streaming-quantile
    dependency.

    Past ``max_samples`` the sample is maintained by Vitter's
    Algorithm R: observation ``n`` replaces a random slot with
    probability ``max_samples/n``, so the retained sample stays a
    uniform draw over the WHOLE run. The previous rotating-slot scheme
    kept only the most recent window, so a long run's p50/p99 silently
    forgot every earlier regime (and the scheme before that stopped
    admitting entirely — quantiles frozen on the run's first minutes).
    The "randomness" is a fixed-seed 64-bit LCG: two integer ops per
    observation, deterministic across runs, no RNG machinery on the
    hot path."""
    __slots__ = ("name", "count", "sum", "_samples", "_max", "_rng")

    def __init__(self, name: str, max_samples: int = 2048):
        self.name = name
        self.count = 0
        self.sum = 0.0
        self._samples: List[float] = []
        self._max = max_samples
        # deterministic per-instrument seed (name-derived, stable)
        self._rng = (0x9E3779B97F4A7C15
                     ^ int.from_bytes(name.encode()[:8].ljust(8, b"\0"),
                                      "little")) & 0xFFFFFFFFFFFFFFFF

    def observe(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.sum += value
        if len(self._samples) < self._max:
            self._samples.append(value)
        else:
            # Algorithm R with an inline LCG (Knuth MMIX constants)
            self._rng = (self._rng * 6364136223846793005
                         + 1442695040888963407) & 0xFFFFFFFFFFFFFFFF
            j = (self._rng >> 11) % self.count
            if j < self._max:
                self._samples[j] = value

    def quantile(self, q: float) -> float:
        if not self._samples:
            return 0.0
        s = sorted(self._samples)
        return s[min(int(q * len(s)), len(s) - 1)]

    def snapshot(self) -> Dict[str, float]:
        return {"count": self.count, "sum": self.sum,
                "p50": self.quantile(0.50), "p99": self.quantile(0.99)}


class MetricsRegistry:
    """One registry per process; named instruments are created on first
    use and must appear in :data:`METRIC_NAMES` with the right type —
    the schema is enforced where the metric is born."""

    def __init__(self, labels: Optional[Dict[str, str]] = None):
        self._lock = threading.Lock()
        self._metrics: Dict[str, Any] = {}
        self.labels: Dict[str, str] = dict(labels or {})

    def set_labels(self, **labels: Any) -> None:
        self.labels.update({k: str(v) for k, v in labels.items()
                            if v is not None})

    def _get(self, name: str, kind: str, factory):
        declared = METRIC_NAMES.get(name)
        if declared is None:
            raise MetricError(f"metric {name!r} not in the pinned "
                              "vocabulary (obs/metrics.py METRIC_NAMES "
                              "+ schemas/metrics.schema.json)")
        if declared != kind:
            raise MetricError(f"metric {name!r} is declared a "
                              f"{declared}, not a {kind}")
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = factory(name)
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, "counter", Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, "gauge", Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, "histogram", Histogram)

    def set_many(self, values: Dict[str, Any]) -> None:
        """Gauges from a metrics dict, keeping only keys the vocabulary
        declares as gauges — the loop feeds its whole log-cadence dict
        and the registry takes the declared slice (unknown keys are the
        caller's own business, not a schema violation)."""
        for k, v in values.items():
            if METRIC_NAMES.get(k) == "gauge" and isinstance(
                    v, (int, float)) and not isinstance(v, bool) \
                    and math.isfinite(float(v)):
                self.gauge(k).set(float(v))

    # -- export --------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = {"labels": dict(self.labels)}
            for name, m in sorted(self._metrics.items()):
                if isinstance(m, Histogram):
                    out[name] = m.snapshot()
                else:
                    out[name] = m.value
            return out

    def to_prometheus(self) -> str:
        label_s = ",".join(f'{k}="{v}"'
                           for k, v in sorted(self.labels.items()))
        label_s = "{" + label_s + "}" if label_s else ""
        lines: List[str] = []
        with self._lock:
            for name, m in sorted(self._metrics.items()):
                kind = METRIC_NAMES[name]
                pname = PROM_PREFIX + name
                lines.append(f"# TYPE {pname} "
                             f"{'summary' if kind == 'histogram' else kind}")
                if isinstance(m, Histogram):
                    snap = m.snapshot()
                    for q in ("0.5", "0.99"):
                        ql = label_s[:-1] + f',quantile="{q}"}}' \
                            if label_s else f'{{quantile="{q}"}}'
                        lines.append(
                            f"{pname}{ql} "
                            f"{snap['p50' if q == '0.5' else 'p99']:.9g}")
                    lines.append(f"{pname}_sum{label_s} {snap['sum']:.9g}")
                    lines.append(f"{pname}_count{label_s} {snap['count']}")
                else:
                    lines.append(f"{pname}{label_s} {m.value:.9g}")
        return "\n".join(lines) + "\n"

    def export(self, obs_dir: str, rank) -> Dict[str, str]:
        """Write both export formats atomically (tmp + rename — a
        scraper must never read a torn file). Returns the paths."""
        os.makedirs(obs_dir, exist_ok=True)
        paths = {}
        for suffix, payload in (
                (".json", json.dumps(self.snapshot(), sort_keys=True,
                                     indent=1)),
                (".prom", self.to_prometheus())):
            path = os.path.join(obs_dir, f"metrics-r{rank}{suffix}")
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                f.write(payload)
            os.replace(tmp, path)
            paths[suffix] = path
        return paths


def pull_jax_counters(reg: MetricsRegistry) -> None:
    """Mirror the perf.cache jax.monitoring counters into the registry
    (host-side dict reads; safe with no backend and cheap enough for
    the log cadence)."""
    try:
        from gke_ray_train_tpu.perf.cache import cache_stats
        s = cache_stats()
        reg.gauge("compile_cache_hits").set(s["hits"])
        reg.gauge("compile_cache_misses").set(s["misses"])
        reg.gauge("compile_time_saved_s").set(s["compile_time_saved_s"])
    except Exception as e:  # noqa: BLE001 - telemetry is best-effort
        logger.debug("cache counters unavailable: %s", e)


def export_serve_stats(reg: MetricsRegistry, stats: Dict[str, Any]) -> None:
    """serve/engine.py ``stats()`` -> the registry, one mapping (the
    TB satellite and the exporter both read the registry, so serving
    latency/occupancy has exactly one computation path)."""
    for src, dst in (("iterations", "serve_iterations_total"),
                     ("refills", "serve_refills_total"),
                     ("completed", "serve_completed_total"),
                     ("adapter_hits", "serve_adapter_hits_total"),
                     ("adapter_misses", "serve_adapter_misses_total"),
                     ("adapter_evictions", "serve_adapter_evictions_total"),
                     ("prefix_hits", "serve_prefix_hits_total"),
                     ("spec_proposed", "serve_spec_proposed_total"),
                     ("spec_accepted", "serve_spec_accepted_total")):
        if src in stats:
            c = reg.counter(dst)
            c.value = float(stats[src])
    for src, dst in (("p50_token_latency_s", "serve_p50_token_latency_s"),
                     ("p99_token_latency_s", "serve_p99_token_latency_s"),
                     ("batch_occupancy", "serve_batch_occupancy")):
        if src in stats:
            reg.gauge(dst).set(float(stats[src]))


def schema_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "schemas", "metrics.schema.json")


def check_schema() -> List[str]:
    """Shipped metric schema <-> code vocabulary, same contract shape
    as events.check_schema. Also cross-checks the goodput_* names
    against train/metrics.py LEDGER_TERMS — the ledger is the one
    source of those terms."""
    findings: List[str] = []
    try:
        with open(schema_path(), encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"metrics schema unreadable: {type(e).__name__}: {e}"]
    declared = doc.get("metrics", {})
    if declared != METRIC_NAMES:
        drift = sorted(set(declared) ^ set(METRIC_NAMES)) or sorted(
            k for k in declared if declared[k] != METRIC_NAMES.get(k))
        findings.append(f"metrics schema drifted from METRIC_NAMES: "
                        f"{drift}")
    try:
        from gke_ray_train_tpu.train.metrics import LEDGER_TERMS
        want = {f"goodput_{t}" for t in LEDGER_TERMS} | {
            "goodput_wall_s", "goodput_frac"}
        have = {k for k in METRIC_NAMES if k.startswith("goodput_")}
        if want != have:
            findings.append(
                f"goodput metric names {sorted(want ^ have)} drifted "
                "from train/metrics.py LEDGER_TERMS")
    except Exception as e:  # noqa: BLE001 - jax may be unimportable
        logger.debug("ledger cross-check skipped: %s", e)
    return findings
