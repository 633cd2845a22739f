"""Profiler hooks (train/profiling.py, SURVEY.md §5.1)."""

import glob
import os

import jax
import jax.numpy as jnp

from gke_ray_train_tpu.train.profiling import (
    TraceProfiler, apply_debug_flags, profiler_from_config)


def test_trace_window_writes_xprof_files(tmp_path):
    logdir = str(tmp_path / "profile")
    prof = TraceProfiler(logdir, start_step=2, num_steps=2)
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((64, 64))
    for step in range(1, 7):
        x = f(x)
        prof.step(step)
    prof.close()
    files = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any("xplane" in p or p.endswith(".pb") or "trace" in p
               for p in files), files


def test_profiler_from_config_off_by_default(tmp_path):
    assert profiler_from_config({}, str(tmp_path)) is None
    p = profiler_from_config({"PROFILE": True, "PROFILE_START_STEP": 3,
                              "PROFILE_NUM_STEPS": 2}, str(tmp_path))
    assert p.start_step == 3 and p.stop_step == 5
    p2 = profiler_from_config({"PROFILE": str(tmp_path / "custom")},
                              str(tmp_path))
    assert p2.logdir.endswith("custom")


def test_run_training_with_profiler(tmp_path):
    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step,
        warmup_cosine_schedule)
    from gke_ray_train_tpu.train.loop import run_training

    cfg = tiny(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32")
    sch = warmup_cosine_schedule(1e-3, 10)
    opt = make_optimizer(sch)
    state = make_train_state(cfg, opt, jax.random.key(0))
    step = make_train_step(cfg, opt, schedule=sch)

    def batches(epoch):
        for i in range(4):
            yield {
                "inputs": jax.random.randint(jax.random.key(i), (2, 16),
                                             0, 64),
                "targets": jax.random.randint(jax.random.key(i + 9),
                                              (2, 16), 0, 64),
                "weights": jnp.ones((2, 16), jnp.float32),
            }

    logdir = str(tmp_path / "prof")
    prof = TraceProfiler(logdir, start_step=1, num_steps=2)
    state, metrics = run_training(state, step, batches, epochs=1,
                                  log_every=2, profiler=prof)
    assert prof._done
    assert os.path.isdir(logdir)


def test_loss_stream_bitwise_with_a_profiler_attached(tmp_path,
                                                      tiny_train_setup):
    """The regions, the in-memory record and the scope table cost no
    numerics: the loss stream with a profiler object attached (and a
    real trace in flight) is bitwise the stream without."""
    import numpy as np

    from gke_ray_train_tpu.obs import trace as obs_trace
    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches

    class Losses:
        def __init__(self):
            self.seen = []

        def log(self, step, metrics):
            if "loss" in metrics:
                self.seen.append((step, metrics["loss"]))

        def log_registry(self, *a, **k):
            pass

        def flush(self):
            pass

        def close(self):
            pass

    def run(profiler):
        _, _, state, step = tiny_train_setup
        log = Losses()
        final, _ = run_training(state, step, _batches(6), epochs=1,
                                log_every=1, prefetch=2, tb_writer=log,
                                profiler=profiler)
        return log.seen, jax.device_get(final.params)

    obs_trace.RECORD.clear()
    try:
        plain, params_plain = run(None)
        # no per-step name without a profiler; what ends once a call is
        # kept whoever listens (ISSUE 34)
        assert {s["name"] for s in obs_trace.RECORD.spans} == {
            "train_loop", "compile"}
        traced, params_traced = run(TraceProfiler(
            str(tmp_path / "prof"), start_step=2, num_steps=2))
        assert any(s["name"] == "step_iter"
                   for s in obs_trace.RECORD.spans)
    finally:
        obs_trace.RECORD.clear()
    assert traced == plain and len(plain) >= 6      # bitwise, not approx
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(params_traced),
        jax.tree_util.tree_leaves(params_plain)))


def test_profile_window_holds_the_programs_names(tmp_path,
                                                 tiny_train_setup):
    """A PROFILE window's host plane carries the loop's regions as
    `grt:<name>` annotations."""
    from jax.profiler import ProfileData

    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches
    _, _, state, step = tiny_train_setup
    logdir = str(tmp_path / "prof")
    run_training(state, step, _batches(6), epochs=1, log_every=1,
                 profiler=TraceProfiler(logdir, start_step=2, num_steps=3))
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("grt:")}
    assert {"grt:step_iter", "grt:data_wait", "grt:step_dispatch",
            "grt:metrics_fetch", "grt:log_emit"} <= names
