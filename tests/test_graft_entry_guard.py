"""The entry points run on the backend jax attaches — or fail.

Earlier rounds probed the accelerator in a subprocess and, when it did
not answer (or had too few devices), re-ran on a virtual CPU mesh and
reported success. On a machine where the chip is simply there, that
turns a broken run into a green one. These tests pin the opposite
contract for ``__graft_entry__.py``, the obs backend stamp and
``chip_smoke.py``: no probe, no subprocess, no fallback tag, and too
few devices is an error.
"""

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, filename):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, filename))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def no_children(monkeypatch):
    """Any attempt to start a child process fails the test."""
    def refuse(*a, **kw):
        raise AssertionError(f"a child process was started: {a[:1]}")
    monkeypatch.setattr(subprocess, "Popen", refuse)
    monkeypatch.setattr(subprocess, "run", refuse)
    monkeypatch.setattr(os, "execv", refuse)
    monkeypatch.setattr(os, "execve", refuse)


def test_dryrun_with_too_few_devices_raises(no_children):
    """8 devices attached (conftest), 16 asked for: an error naming
    both numbers — not a re-exec on a bigger virtual mesh."""
    entry_mod = _load("graft_entry_under_test", "__graft_entry__.py")
    with pytest.raises(RuntimeError, match=r"needs 16 devices.*has 8"):
        entry_mod.dryrun_multichip(16)


def test_entry_just_builds_and_spawns_nothing(no_children, capsys):
    entry_mod = _load("graft_entry_under_test", "__graft_entry__.py")
    # the whole function surface: nothing left that probes or respawns
    import types
    assert {n for n, v in vars(entry_mod).items()
            if isinstance(v, types.FunctionType)} == {
        "entry", "dryrun_multichip", "_flagship_cfg"}
    assert not hasattr(entry_mod, "subprocess")
    fn, args = entry_mod.entry()
    assert jax.jit(fn)(*args).shape == (2, 128, 512)
    assert capsys.readouterr().out == ""     # no banner of any kind


def test_one_benchmark_entry():
    """``BENCHMARK.json`` names the one benchmark, and its script is
    there. The benchmark it replaced (a root-level script selected by an
    environment variable, with a shell script that recorded its
    baselines) is named by nothing but the accounts of how the repo got
    here: no code, config, workflow or user-facing document. Walks the
    checkout as it lies on disk (no ``git`` in the driver's copy)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    script = next(a for a in command if a.endswith(".py"))
    assert os.path.isfile(os.path.join(REPO, script)), script

    # spelled in pieces so that this file does not name them either
    gone = ("bench" + ".py", "BENCH" + "_MODE", "record" + "_baselines")
    accounts = re.compile(
        r"^(CHANGES|ROADMAP|PERF|SURVEY|PAPER\w*|ISSUE|REVIEW)\.md$"
        r"|^PERF_LEDGER\.jsonl$")
    # what the tools leave behind, not the checkout's own
    left_behind = {"__pycache__", "chiprun_out"}
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in left_behind]
        for name in files:
            if root == REPO and accounts.match(name):
                continue
            with open(os.path.join(root, name), errors="ignore") as f:
                text = f.read()
            hits += [f"{os.path.relpath(os.path.join(root, name), REPO)}"
                     f": {g}" for g in gone if g in text]
    assert not hits, hits


def test_obs_backend_stamp_is_the_live_backend(monkeypatch):
    from gke_ray_train_tpu.obs.runtime import current_backend
    monkeypatch.setenv("BENCH_CPU_FALLBACK", "1")
    assert current_backend() == jax.default_backend()


def test_one_backend_test_decides_every_device_choice(monkeypatch):
    """flash vs XLA, compiled vs interpreted Pallas and the OVERLAP=xla
    compiler options all follow ``parallel.mesh.on_tpu`` and nothing
    else."""
    import gke_ray_train_tpu.parallel.mesh as mesh_mod
    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.ops.flash_attention import interpret_default
    from gke_ray_train_tpu.plan import (
        XLA_OVERLAP_OPTIONS, XLA_TPU_OPTIONS, ExecutionPlan,
        overlap_compiler_options, tpu_compiler_options)
    plan = ExecutionPlan(overlap="xla")
    assert not mesh_mod.on_tpu()
    assert tiny().resolved_attn_impl == "xla"
    assert interpret_default(None) is True
    assert overlap_compiler_options(plan) is None
    assert tpu_compiler_options(plan) is None
    monkeypatch.setattr(mesh_mod, "on_tpu", lambda: True)
    assert tiny().resolved_attn_impl == "flash"
    assert interpret_default(None) is False
    assert overlap_compiler_options(plan) == XLA_OVERLAP_OPTIONS
    assert overlap_compiler_options(ExecutionPlan(overlap="off")) is None
    # what the compile surface passes: the TPU's own options always,
    # the scheduler's under overlap="xla"
    assert tpu_compiler_options(plan) == {**XLA_TPU_OPTIONS,
                                          **XLA_OVERLAP_OPTIONS}
    assert tpu_compiler_options(ExecutionPlan(overlap="off")) == (
        XLA_TPU_OPTIONS)


def test_chip_smoke_refuses_a_cpu_and_a_bare_directory(tmp_path):
    """``chip_smoke.py`` exits non-zero with no result line when jax
    attaches no TPU (naming the platform it found), and in a directory
    that holds nothing else of the repo."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, cwd=REPO, env=env,
                       timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu', not 'tpu'" in r.stderr
    assert r.stdout == ""

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, cwd=tmp_path,
                       env=env, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The last stdout line of a pass is ``{"ok", "device": {"platform",
    "kind", "count"}}`` and nothing else; the facts go on the summary
    line before it."""
    smoke = _load("chip_smoke", "chip_smoke.py")
    d = jax.devices()[0]
    line = smoke.result_line({"platform": d.platform,
                              "kind": d.device_kind, "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": 1}}
