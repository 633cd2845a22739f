"""The driver contract for bench.py: exactly ONE JSON line on stdout
with metric/value/unit/vs_baseline, exit code 0 — on any backend
(the CPU fallback keeps the mode testable in CI). Also pins the mode
registry against the docs/remat-default tables drifting."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_mode_registry_consistent():
    src = open(os.path.join(REPO, "bench.py")).read()
    # the dispatch dict and the remat-defaults table must agree
    modes = set(re.findall(r'"([a-z0-9-]+)":\s*bench_\w+', src))
    table = re.search(r"_REMAT_DEFAULTS = \{(.*?)\}", src, re.S).group(1)
    remat_defaults = set(re.findall(r'"([a-z0-9-]+)":', table))
    assert remat_defaults <= modes, (
        f"_REMAT_DEFAULTS keys {remat_defaults - modes} not in the "
        f"mode registry {modes}")
    # every mode the quickstart advertises exists
    readme = open(os.path.join(REPO, "README.md")).read()
    for m in re.findall(r"BENCH_MODE=([a-z0-9-]+) python bench\.py",
                        readme):
        assert m in modes, f"README advertises unknown mode {m!r}"


def test_goodput_ledger_schema_pinned():
    """The goodput ledger's term set is a cross-artifact contract: the
    loop fills it, the trainer reconciles it, BENCH_MODE=elastic and
    record_baselines.sh persist it, and the README documents it. Pin
    the schema so a renamed term fails here instead of silently
    un-reconciling old records."""
    from gke_ray_train_tpu.train.metrics import (
        LEDGER_TERMS, finish_ledger, sum_ledgers)
    assert LEDGER_TERMS == ("compile_s", "restore_s", "fast_forward_s",
                            "data_stall_s", "eval_ckpt_stall_s",
                            "ckpt_async_s", "peer_restore_s",
                            "step_s", "lost_s")
    # reconciliation identity: terms sum to wall-clock by construction
    led = finish_ledger({"compile_s": 1.0, "step_s": 2.5}, 5.0)
    assert abs(sum(led[t] for t in LEDGER_TERMS) - led["wall_s"]) < 1e-9
    assert led["lost_s"] == 1.5
    total = sum_ledgers([led, finish_ledger(None, 3.0)])
    assert total["wall_s"] == 8.0
    assert total["goodput_frac"] == total["step_s"] / total["wall_s"]
    # BENCH_MODE=elastic pins the same terms on its record
    src = open(os.path.join(REPO, "bench.py")).read()
    assert '"elastic": bench_elastic' in src
    assert "LEDGER_TERMS" in src


def test_bench_dcn_mode_registered():
    """BENCH_MODE=dcn is in the dispatch registry and its record pins
    the per-arm network fields (the fast half of the schema pin; the
    slow half runs the subprocess)."""
    src = open(os.path.join(REPO, "bench.py")).read()
    assert '"dcn": bench_dcn' in src
    for field in ("losses_bitwise_equal", "dcn_bytes_flat",
                  "dcn_bytes_hier", "dcn_bytes_compressed",
                  "ici_bytes_flat", "ici_bytes_hier",
                  "overlap_frac_flat", "overlap_frac_hier"):
        assert f'"{field}"' in src, field


def test_bench_autotune_mode_registered():
    """BENCH_MODE=autotune is in the dispatch registry and its record
    pins the default-vs-tuned schema (the fast half; the slow half
    runs the subprocess)."""
    src = open(os.path.join(REPO, "bench.py")).read()
    assert '"autotune": bench_autotune' in src
    for field in ("modeled_step_s_default", "modeled_step_s_tuned",
                  "winner_diff", "plan_fingerprint_default",
                  "plan_fingerprint_tuned",
                  "exposed_collective_bytes_default",
                  "exposed_collective_bytes_tuned",
                  "cost_report_default", "cost_report_tuned",
                  "loss_stream_default", "loss_stream_tuned",
                  "loss_trajectory_valid"):
        assert f'"{field}"' in src, field


@pytest.mark.slow
def test_bench_autotune_record_shape():
    """BENCH_MODE=autotune emits ONE valid record: the winner never
    loses to the default (it is candidate 0 of its own space), both
    arms' cost evidence rides the record, and the tuned arm's real
    loss stream validates against the default trajectory."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BENCH_")}
    env.update(BENCH_MODE="autotune", JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO, COMPILE_CACHE="0")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, cwd=REPO,
                       timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["unit"] == "x" and rec["value"] >= 1.0
    assert rec["modeled_step_s_tuned"] <= rec["modeled_step_s_default"]
    assert rec["loss_trajectory_valid"] is True
    assert all(v == v for v in rec["loss_stream_tuned"])
    assert rec["plan_fingerprint_default"] \
        and rec["plan_fingerprint_tuned"]
    assert rec["cost_report_default"]["collective_bytes"] >= 0
    assert rec["space"]["scored"] >= rec["space"]["compiled"] >= 2


@pytest.mark.slow
def test_bench_dcn_record_shape():
    """BENCH_MODE=dcn emits ONE valid record: bitwise flat-vs-hier
    loss streams asserted on-record, per-arm ici/dcn bytes, and the
    DCN shrink factor as the value (~ici_size on the 2x4 mesh)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BENCH_")}
    env.update(BENCH_MODE="dcn", JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO, COMPILE_CACHE="0")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, cwd=REPO,
                       timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["losses_bitwise_equal"] is True
    assert rec["compressed_within_5pct"] is True
    assert rec["dcn_bytes_hier"] < rec["dcn_bytes_flat"]
    assert rec["dcn_bytes_compressed"] < rec["dcn_bytes_hier"]
    # value = the DCN shrink factor; ici_size = 4 on the 2x4 mesh
    assert 3.0 <= rec["value"] <= 4.5
    assert rec["unit"] == "x"
    assert rec["plan_fingerprint"]


@pytest.mark.slow
def test_bench_elastic_record_shape():
    """BENCH_MODE=elastic emits one valid tagged record whose goodput
    ledger carries exactly the pinned terms (+ wall_s/goodput_frac) and
    whose events classify the shrink/grow as preemptions."""
    from gke_ray_train_tpu.train.metrics import LEDGER_TERMS
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BENCH_")}
    env.update(BENCH_MODE="elastic", JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO, RETRY_BACKOFF_S="0", COMPILE_CACHE="0")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, cwd=REPO,
                       timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["value"] > 0
    assert set(rec["goodput"]) == set(LEDGER_TERMS) | {"wall_s",
                                                       "goodput_frac"}
    assert rec["mesh_devices_per_attempt"] == [8, 4, 8]
    assert len(rec["events"]) == rec["attempts"] == 3
    assert [e.get("event") for e in rec["events"]] == \
        ["shrink", "grow", None]
    assert rec["preemptions"] == 2
    assert rec["time_to_first_step_after_shrink_s"] > 0
    assert rec["plan_fingerprint"]


@pytest.mark.slow
def test_bench_emits_one_json_line():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BENCH_")}  # deterministic default mode
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, cwd=REPO,
                       timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected ONE stdout line, got: {lines}"
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, rec
    assert rec["value"] > 0
