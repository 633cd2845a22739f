"""Compile-once layer (perf/): persistent cache, AOT executables, and
the cost/memory budget harness — all on the 8-fake-device CPU mesh.

The contract under test (ISSUE 4):
- a second build of an identical train step performs ZERO new XLA
  compilations (persistent-cache hit, counted via JAX's own miss
  counters);
- an AOT serialize→deserialize round-trip executes bitwise-identically
  to the jit-built step;
- the budget comparator catches a remat policy silently turning off
  (peak-memory jump) and an extra collective appearing in the grad
  path (with the offending HLO delta in the message);
- the checked-in budgets under tests/budgets/ pass on main.
"""

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gke_ray_train_tpu.perf.cache as perf_cache
from gke_ray_train_tpu.perf.budget import (
    BUDGET_DIR, DEFAULT_TOLERANCES, PRESETS, BudgetViolation,
    all_preset_names, assert_within_budget, budget_path,
    build_preset_report, build_preset_step, compare_to_budget, load_budget,
    write_budget)
from gke_ray_train_tpu.perf.cache import (
    GuardedStep, aot_signature, build_or_load_step, cache_stats,
    enable_persistent_cache, load_executable, save_executable)
from gke_ray_train_tpu.perf.costs import (
    CHIP_SPECS, assert_state_donation, collective_stats, step_cost_report)
from gke_ray_train_tpu.models import tiny
from gke_ray_train_tpu.train import (
    make_eval_step, make_optimizer, make_train_state, make_train_step)
from gke_ray_train_tpu.train.step import batch_shardings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_sandbox(tmp_path, monkeypatch):
    """Route the persistent cache into tmp and restore JAX's global
    cache config afterwards — these tests mutate process-wide state the
    rest of the suite must not inherit."""
    monkeypatch.setattr(perf_cache, "_ENABLED_DIR", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("COMPILE_CACHE_DIR", raising=False)
    # conftest disables the cache suite-wide (no persistent writes from
    # ordinary tests); these tests opt back in, sandboxed
    monkeypatch.setenv("COMPILE_CACHE", "1")
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", None)
    from jax._src import compilation_cache
    compilation_cache.reset_cache()


def _tiny_setup(mesh, *, donate=False, remat=True, B=8, S=64):
    cfg = tiny(d_model=64, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=128,
               vocab_size=256, max_seq_len=S, remat=remat)
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, donate=donate)
    batch = jax.device_put(
        {"inputs": jnp.zeros((B, S), jnp.int32),
         "targets": jnp.zeros((B, S), jnp.int32),
         "weights": jnp.ones((B, S), jnp.float32)},
        batch_shardings(mesh))
    return cfg, opt, state, step, batch


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

def test_cache_hit_second_build_compiles_nothing(cache_sandbox, fsdp_mesh):
    """The headline contract: rebuilding the SAME step from an identical
    config costs zero new XLA compilations — every compile is a
    persistent-cache hit (JAX's own miss counters are the witness)."""
    enabled = enable_persistent_cache(str(cache_sandbox / "cache"))
    # the directory itself, no fingerprint subdirectory under it
    assert enabled == str(cache_sandbox / "cache")
    # drop in-memory executables BEFORE the cold build: helpers compiled
    # by earlier tests would otherwise be reused (and never persisted to
    # this fresh cache dir), then MISS on the rebuild below
    jax.clear_caches()
    s0 = cache_stats()
    c1, _, _ = build_preset_step("tiny_fsdp8")
    s1 = cache_stats()
    assert s1["misses"] > s0["misses"], "cold build must populate the cache"
    jax.clear_caches()  # drop in-memory jit caches: force a real rebuild
    c2, state, batch = build_preset_step("tiny_fsdp8")
    s2 = cache_stats()
    assert s2["misses"] == s1["misses"], (
        "identical rebuild performed NEW compilations — persistent cache "
        f"missed ({s2['misses'] - s1['misses']} misses)")
    assert s2["hits"] > s1["hits"]
    assert s2["dir"] == enabled and os.listdir(enabled)
    # and the cache-built executable actually runs
    _, m = c2(state, batch)
    assert np.isfinite(float(jax.device_get(m["loss"])))


def test_an_executable_named_under_another_vocabulary_is_not_served(
        cache_sandbox, monkeypatch):
    """jax leaves metadata (the named_scope path) out of the cache key,
    so the step an AOT build keeps is keyed with the names' digest
    (perf/cache.py::salted_cache_key): same names hit, other names — or
    a scope that moved — compile anew, and the table reads the names of
    the code that runs."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    from gke_ray_train_tpu.perf.cache import build_or_load_step
    enable_persistent_cache(str(cache_sandbox / "cache"))

    def build():
        jax.clear_caches()
        before = cache_stats()

        def scoped_step(x):
            with obs_trace.scope("mlp/down"):
                return jnp.tanh(x @ x.T).sum()
        step = build_or_load_step(jax.jit(scoped_step),
                                  jnp.ones((8, 8), jnp.float32),
                                  label="salted step")
        after = cache_stats()
        return step, (after["hits"] - before["hits"],
                      after["misses"] - before["misses"])

    _, cold = build()                 # (hits, misses); the operand's
    assert cold[1] >= 1               # own small programs count too
    step, warm = build()
    assert warm[0] >= 1 and warm[1] == 0
    monkeypatch.setattr(obs_trace, "SCOPE_VERSION",
                        obs_trace.SCOPE_VERSION + 1)
    _, moved = build()
    assert moved == (warm[0] - 1, 1)  # the step alone compiles anew
    obs_trace.RECORD.clear()
    step.note_scope_table()
    table = obs_trace.RECORD.scope_tables.pop("salted step")
    assert "mlp/down" in {obs_trace.scope_path(op)
                          for op in table.values()}
    obs_trace.RECORD.clear()


def test_unusable_cache_dir_raises(cache_sandbox):
    """No fallback to a home directory and no silent disable: a cache
    directory that cannot be written is an error."""
    with pytest.raises(RuntimeError, match="unusable"):
        enable_persistent_cache("/proc/definitely/not/writable")
    assert cache_stats()["dir"] is None


def test_cache_dir_from_outside_wins_and_is_not_set_in_code(
        cache_sandbox, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is JAX's to read: it beats the explicit
    argument, the plan's COMPILE_CACHE_DIR and the env key, gets no
    subdirectory, and the program never names a directory itself."""
    from gke_ray_train_tpu.plan import ExecutionPlan
    outside = str(cache_sandbox / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.setenv("COMPILE_CACHE_DIR", str(cache_sandbox / "env_key"))
    plan = ExecutionPlan.from_config(
        {"COMPILE_CACHE_DIR": str(cache_sandbox / "config_key")})
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    got = enable_persistent_cache(str(cache_sandbox / "argument"),
                                  plan=plan)
    assert got == outside and os.path.isdir(outside)
    assert "jax_compilation_cache_dir" not in updates
    # the floors are still dropped and the counters still installed
    assert "jax_persistent_cache_min_entry_size_bytes" in updates
    assert cache_stats()["dir"] == outside
    assert not os.path.exists(cache_sandbox / "argument")


def test_default_cache_dir_is_fixed_inside_the_checkout(cache_sandbox,
                                                        monkeypatch):
    """With nothing named anywhere the cache lives at <checkout>/
    .jax_cache, and the answer does not move when the backend comes up
    (it used to carry a topology digest that did)."""
    want = os.path.join(REPO, ".jax_cache")
    for backend_up in (False, True):
        monkeypatch.setattr(perf_cache, "_backend_initialized",
                            lambda up=backend_up: up)
        assert perf_cache.resolve_cache_dir() == (want, False)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # resolution order below the outside variable
    monkeypatch.setenv("COMPILE_CACHE_DIR", "/env/key")
    assert perf_cache.resolve_cache_dir() == ("/env/key", False)
    assert perf_cache.resolve_cache_dir("/argument") == ("/argument", False)


def test_enable_respects_kill_switch(cache_sandbox, monkeypatch):
    monkeypatch.setenv("COMPILE_CACHE", "0")
    assert enable_persistent_cache(str(cache_sandbox / "x")) is None


# ---------------------------------------------------------------------------
# AOT serialize → deserialize
# ---------------------------------------------------------------------------

def test_aot_roundtrip_bitwise_identical(tmp_path, fsdp_mesh):
    """serialize→deserialize must execute bit-for-bit like the jit path
    (same executable, not a recompile that might reassociate floats)."""
    _, _, state, step, batch = _tiny_setup(fsdp_mesh)
    compiled = step.lower(state, batch).compile()
    path = str(tmp_path / "step.aot")
    key = aot_signature(state, batch)
    assert save_executable(compiled, path, key)
    loaded = load_executable(path, key)
    assert loaded is not None
    st_a, m_a = compiled(state, batch)
    st_b, m_b = loaded(state, batch)
    assert jnp.array_equal(m_a["loss"], m_b["loss"])
    for x, y in zip(jax.tree.leaves(st_a.params),
                    jax.tree.leaves(st_b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # stale sidecar (different signature) must be refused, not loaded
    assert load_executable(path, "not-the-key") is None


def test_build_or_load_step_deserializes_second_time(tmp_path, fsdp_mesh):
    _, _, state, step, batch = _tiny_setup(fsdp_mesh)
    sidecar = str(tmp_path / "train_step.bin")
    g1 = build_or_load_step(step, state, batch, sidecar=sidecar)
    assert g1.info["source"] == "compiled"
    assert os.path.exists(sidecar)
    g2 = build_or_load_step(step, state, batch, sidecar=sidecar)
    assert g2.info["source"] == "deserialized"
    _, m1 = g1(state, batch)
    _, m2 = g2(state, batch)
    assert jnp.array_equal(m1["loss"], m2["loss"])


def test_guarded_step_falls_back_on_rejected_call():
    class Exploding:
        def __call__(self, *a):
            raise ValueError("layout mismatch")

    calls = []
    guarded = GuardedStep(Exploding(), lambda *a: calls.append(a) or "jit",
                          info={"source": "deserialized"})
    assert guarded(1, 2) == "jit"  # falls back, does not raise
    assert guarded(3, 4) == "jit"  # and stays fallen back
    assert len(calls) == 2


def test_guarded_step_reraises_when_donated_args_consumed():
    """A failure AFTER dispatch may have consumed donated buffers —
    retrying the jit path would die on deleted arrays and bury the real
    error, so the original exception must surface instead."""
    class DonatedLeaf:
        def is_deleted(self):
            return True

    class ExplodesMidExecution:
        def __call__(self, *a):
            raise RuntimeError("RESOURCE_EXHAUSTED: the real error")

    guarded = GuardedStep(ExplodesMidExecution(), lambda *a: "jit",
                          info={})
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        guarded((DonatedLeaf(),))


# ---------------------------------------------------------------------------
# cost reports
# ---------------------------------------------------------------------------

def test_collective_stats_parses_hlo_text():
    hlo = """
  %ar = f32[64,128]{1,0} all-reduce(f32[64,128]{1,0} %p0), replica_groups={}
  %ag = f32[512]{0} all-gather(f32[64]{0} %p1), dimensions={0}
  %ars = (f32[8]{0}, f32[8]{0}) all-reduce-start(f32[8]{0} %x, f32[8]{0} %y)
  %ard = f32[8]{0} all-reduce-done(%ars)
  %add = f32[8]{0} add(%p2, %p3)
"""
    counts, nbytes, lines = collective_stats(hlo)
    assert counts["all-reduce"] == 2  # -start counted, -done not
    assert counts["all-gather"] == 1
    assert counts["all-to-all"] == 0
    assert nbytes == 64 * 128 * 4 + 512 * 4 + 2 * 8 * 4
    assert len(lines) == 3


def test_step_cost_report_on_fsdp_mesh(fsdp_mesh):
    compiled, _, _ = build_preset_step("tiny_fsdp8")
    rep = step_cost_report(compiled, tokens_per_step=8 * 64)
    assert rep.flops > 0 and rep.bytes_accessed > 0
    assert rep.temp_bytes > 0 and rep.argument_bytes > 0
    assert rep.collective_counts["all-reduce"] > 0, \
        "an fsdp train step with no all-reduce is not a train step"
    assert rep.flops_per_token() == pytest.approx(
        rep.flops * rep.n_devices / (8 * 64))
    ceil = rep.ceilings(CHIP_SPECS["v5e"])
    assert 0 < ceil["mfu_ceiling"] <= 1.0
    # round-trips through the JSON form the budgets store
    rt = type(rep).from_dict(json.loads(json.dumps(rep.to_dict())))
    assert rt.flops == rep.flops
    assert rt.collective_counts == rep.collective_counts


def test_state_donation_asserted_via_memory_analysis(fsdp_mesh):
    """donate_argnums=(0,) must actually alias the state into its
    updated outputs — memory_analysis is the witness (works on the CPU
    mesh too: XLA reports the aliased bytes it committed to)."""
    _, _, state, step, batch = _tiny_setup(fsdp_mesh, donate=True)
    compiled = step.lower(state, batch).compile()
    aliased = assert_state_donation(compiled, state)
    assert aliased > 0


def test_donate_batch_argnums_plumbing():
    cfg = tiny()
    opt = make_optimizer(1e-3)
    assert make_train_step(cfg, opt).donate_argnums == (0, 1)
    assert make_train_step(cfg, opt,
                           donate_batch=False).donate_argnums == (0,)
    assert make_train_step(cfg, opt, donate=False).donate_argnums == ()


# ---------------------------------------------------------------------------
# budgets
# ---------------------------------------------------------------------------

def test_comparator_unit_tolerances():
    base = {"flops": 1000.0, "temp_bytes": 1000,
            "collective_counts": {"all-reduce": 2},
            "collective_lines": ["%a = f32[8]{0} all-reduce(%x)",
                                 "%b = f32[8]{0} all-reduce(%y)"]}
    assert compare_to_budget(dict(base), base) == []
    drift = dict(base, flops=1080.0)  # +8% > 5% tolerance
    assert any("flops" in v for v in compare_to_budget(drift, base))
    shrunk = dict(base, flops=900.0)  # two-sided: -10% flags too
    assert any("flops" in v for v in compare_to_budget(shrunk, base))
    within = dict(base, temp_bytes=1100)  # +10% < 25% tolerance
    assert compare_to_budget(within, base) == []


def test_comparator_prints_hlo_delta_for_extra_collective():
    base = {"collective_counts": {"all-reduce": 1},
            "collective_lines": ["%a = f32[8]{0} all-reduce(%x)"]}
    got = {"collective_counts": {"all-reduce": 2},
           "collective_lines": ["%a = f32[8]{0} all-reduce(%x)",
                                "%evil = f32[99]{0} all-reduce(%y)"]}
    viols = compare_to_budget(got, base)
    assert any("collective counts changed" in v for v in viols)
    assert any("f32[99]" in v for v in viols), \
        "the offending HLO line must be named, not just counted"


def test_checked_in_budgets_pass_on_main(fsdp_mesh):
    """Every preset's freshly-compiled report must sit within its
    checked-in budget. BUDGET_UPDATE=1 re-baselines instead (the
    documented intentional-change workflow)."""
    for name in PRESETS:
        rep = build_preset_report(name)
        path = budget_path(name)
        if os.environ.get("BUDGET_UPDATE") == "1":
            write_budget(rep, path, preset=name)
            continue
        assert os.path.exists(path), (
            f"missing budget {path}; record it: python -m "
            "gke_ray_train_tpu.perf.budget record")
        assert_within_budget(rep, path)


def test_budget_documents_hold_counts_only():
    """A compile on XLA:CPU gives flops, bytes and collectives: counts.
    No budget document and no tolerance carries a time (``*_s``) or a
    rate (``*_per_s``), and the documents were recorded together, under
    the jax that is installed."""
    timed = re.compile(r"_s$|_per_s(_|$)")
    paths = sorted(glob.glob(os.path.join(BUDGET_DIR, "*.json")))
    assert [os.path.basename(p)[:-5] for p in paths] == \
        sorted(all_preset_names())
    docs = {p: load_budget(p) for p in paths}
    named = {k for doc in docs.values()
             for k in list(doc) + list(doc.get("tolerances", {}))}
    assert not [k for k in named | set(DEFAULT_TOLERANCES)
                if timed.search(k)]
    assert "tokens_per_step" in named          # a count: let be
    assert {doc["_recorded_with"]["jax"] for doc in docs.values()} == \
        {jax.__version__}


def test_budget_catches_remat_silently_off(fsdp_mesh):
    """Flipping remat=False drops flops (no recompute) and roughly
    doubles peak temp memory — the budget harness must scream."""
    rep = build_preset_report("tiny_fsdp8", remat=False)
    with pytest.raises(BudgetViolation) as e:
        assert_within_budget(rep, budget_path("tiny_fsdp8"))
    assert "temp_bytes" in str(e.value)


def test_budget_catches_extra_collective_in_grad_path(fsdp_mesh):
    """An extra replicated reduction over fsdp-sharded params smuggles
    extra all-reduce/all-gather ops into the compiled step; the
    comparator must flag the count change and print the HLO delta."""
    def wrap(inner):
        def with_extra(state, batch):
            st, m = inner(state, batch)
            m = dict(m)
            m["pnorm2"] = sum(jnp.vdot(x, x)
                              for x in jax.tree.leaves(st.params))
            return st, m
        return with_extra

    compiled, _, _ = build_preset_step("tiny_fsdp8", wrap=wrap)
    rep = step_cost_report(compiled, tokens_per_step=8 * 64)
    viols = compare_to_budget(rep, load_budget(budget_path("tiny_fsdp8")))
    assert any("collective counts changed" in v for v in viols), viols
    assert any(v.strip().startswith("HLO +") for v in viols), viols


# ---------------------------------------------------------------------------
# eval-step sharding contract
# ---------------------------------------------------------------------------

def test_eval_step_pinned_shardings_trace_once(fsdp_mesh, monkeypatch):
    """With explicit batch_shardings, eval compiles ONCE: numpy rows,
    batch-sharded arrays and replicated arrays all dispatch into the
    same executable (no retrace per input layout, no silent
    replication)."""
    import gke_ray_train_tpu.train.step as stepmod
    from jax.sharding import NamedSharding, PartitionSpec as P

    traces = []
    real_forward = stepmod.forward

    def counting_forward(*a, **k):
        traces.append(1)
        return real_forward(*a, **k)

    monkeypatch.setattr(stepmod, "forward", counting_forward)
    cfg = tiny(d_model=64, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=128,
               vocab_size=256, max_seq_len=64)
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=fsdp_mesh)
    bs = batch_shardings(fsdp_mesh)
    ev = make_eval_step(cfg, mesh=fsdp_mesh, batch_shardings=bs)

    B, S = 8, 64
    np_batch = {"inputs": np.zeros((B, S), np.int32),
                "targets": np.zeros((B, S), np.int32),
                "weights": np.ones((B, S), np.float32)}
    placed = jax.device_put(np_batch, bs)
    replicated = jax.device_put(
        np_batch, {k: NamedSharding(fsdp_mesh, P()) for k in np_batch})

    outs = [ev(state, b) for b in (np_batch, placed)]
    assert len(traces) == 1, (
        f"eval retraced {len(traces)} times across input layouts")
    assert float(outs[1][0]) == float(outs[0][0])
    assert float(outs[1][1]) == float(outs[0][1])
    # a committed-but-replicated batch is REJECTED loudly — the pinned
    # contract turns silent replication into an error, not a retrace
    with pytest.raises(ValueError, match="[Ss]harding"):
        ev(state, replicated)
    # the one executable consumes a batch-SHARDED layout, not replicated
    in_shardings = ev.lower(state, placed).compile().input_shardings[0]
    spec = in_shardings[1]["inputs"].spec
    assert spec and spec[0] is not None, (
        f"eval batch silently replicated: {spec}")


# ---------------------------------------------------------------------------
# loop metrics
# ---------------------------------------------------------------------------

def test_run_training_reports_compile_metrics():
    from gke_ray_train_tpu.train.loop import run_training
    cfg = tiny()
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0))
    step = make_train_step(cfg, opt, donate=False)

    def batches(epoch):
        for i in range(2):
            k = jax.random.key(i)
            yield {"inputs": jax.random.randint(k, (4, 16), 0,
                                                cfg.vocab_size),
                   "targets": jax.random.randint(k, (4, 16), 0,
                                                 cfg.vocab_size),
                   "weights": jnp.ones((4, 16), jnp.float32)}

    _, metrics = run_training(state, step, batches, epochs=1)
    assert metrics["compile_s"] > 0
    assert metrics["restart_to_first_step_s"] >= metrics["compile_s"]


# ---------------------------------------------------------------------------
# the build on the record (ISSUE 34): jax's own seconds, the cache's
# verdict and XLA's memory on the build's spans
# ---------------------------------------------------------------------------

def _build_spans():
    """{name: span} of the record, which is then emptied."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    spans = {s["name"]: s for s in obs_trace.RECORD.spans}
    obs_trace.RECORD.clear()
    return spans


def _small_jit():
    def small_step(x):
        return jnp.tanh(x @ x.T).sum()
    return jax.jit(small_step)


def test_step_compile_reads_miss_then_hit_over_two_builds(cache_sandbox):
    from gke_ray_train_tpu.obs import trace as obs_trace
    enable_persistent_cache(str(cache_sandbox / "cache"))
    obs_trace.RECORD.clear()
    seen = []
    for _ in range(2):
        jax.clear_caches()           # drop the in-memory executable
        step = build_or_load_step(_small_jit(),
                                  jnp.ones((8, 8), jnp.float32),
                                  label="twice built")
        comp = _build_spans()["step_compile"]
        seen.append(comp["cache"])
        # `source` keeps its meaning (no sidecar: compiled both times);
        # `cache` says which of the two compiles built anything
        assert step.info["source"] == "compiled"
        assert step.info["cache"] == comp["cache"]
        assert comp["retrieval_s"] >= 0.0
        if comp["cache"] == "hit":
            assert comp["backend_compile_s"] == 0.0
        else:
            assert 0.0 < comp["backend_compile_s"] \
                <= comp["t1"] - comp["t0"]
    assert seen == ["miss", "hit"]


def test_step_compile_says_no_cache_where_none_is_in_use():
    from gke_ray_train_tpu.obs import trace as obs_trace
    obs_trace.RECORD.clear()
    jax.clear_caches()
    step = build_or_load_step(_small_jit(), jnp.ones((8, 8), jnp.float32),
                              label="uncached")
    comp = _build_spans()["step_compile"]
    assert comp["cache"] is None and step.info["cache"] is None
    assert comp["backend_compile_s"] > 0.0


def test_step_lower_carries_jax_s_own_seconds_once(fsdp_mesh):
    """A jitted function traced inside the step reports a trace of its
    own; the region counts the outermost alone, so the two parts never
    pass the region's seconds."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    inner = jax.jit(lambda x: jnp.tanh(x) * 2.0)

    def outer(x):
        return inner(inner(x) @ x.T).sum()
    obs_trace.RECORD.clear()
    jax.clear_caches()
    before = cache_stats()
    build_or_load_step(jax.jit(outer), jnp.ones((8, 8), jnp.float32),
                       label="nested")
    low = _build_spans()["step_lower"]
    assert low["trace_s"] > 0.0 and low["to_mlir_s"] > 0.0
    assert low["trace_s"] + low["to_mlir_s"] <= \
        (low["t1"] - low["t0"]) * 1.05 + 1e-3
    # the process's totals moved by the same seconds
    after = cache_stats()
    assert after["trace_s"] - before["trace_s"] >= low["trace_s"] - 1e-9


def test_timed_blocks_count_the_outermost_alone():
    """The listener by hand: jax reports a block's start as a scalar
    and its end as a duration; a block inside another adds nothing."""
    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    mlir_ev = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    before = cache_stats()
    perf_cache._on_scalar(trace_ev, 0.0)          # outer opens
    perf_cache._on_scalar(trace_ev, 0.0)          # inner opens
    perf_cache._on_duration(trace_ev, 1.0)        # inner ends
    perf_cache._on_duration(trace_ev, 3.0)        # outer ends
    perf_cache._on_scalar(mlir_ev, 0.0)
    perf_cache._on_scalar(trace_ev, 0.0)          # a trace in a lowering
    perf_cache._on_duration(trace_ev, 0.5)
    perf_cache._on_duration(mlir_ev, 2.0)
    after = cache_stats()
    assert after["trace_s"] - before["trace_s"] == pytest.approx(3.0)
    assert after["to_mlir_s"] - before["to_mlir_s"] == pytest.approx(2.0)


XLA_MEMORY_KEYS = {"peak", "arguments", "outputs", "aliased",
                   "temporaries", "code", "limit"}


@pytest.mark.parametrize("sidecar", [False, True],
                         ids=["compiled", "deserialized"])
def test_step_build_carries_xla_memory(tmp_path, fsdp_mesh, sidecar):
    from gke_ray_train_tpu.obs import trace as obs_trace
    _, _, state, step, batch = _tiny_setup(fsdp_mesh)
    path = str(tmp_path / "step.bin") if sidecar else None
    if sidecar:
        build_or_load_step(step, state, batch, sidecar=path)
    obs_trace.RECORD.clear()
    built = build_or_load_step(step, state, batch, sidecar=path)
    span = _build_spans()["step_build"]
    assert span["source"] == ("deserialized" if sidecar else "compiled")
    memory = span["xla_memory"]
    assert memory == built.info["xla_memory"]
    # XLA:CPU may give no analysis: {} then, else all seven
    assert memory == {} or set(memory) == XLA_MEMORY_KEYS
    if memory:
        assert memory["limit"] is None      # XLA:CPU reports no limit
        stats = built._compiled.memory_analysis()
        assert memory["peak"] == stats.peak_memory_in_bytes
        assert memory["arguments"] == stats.argument_size_in_bytes
        assert memory["temporaries"] == stats.temp_size_in_bytes
        assert memory["code"] == stats.generated_code_size_in_bytes


def test_past_peak_judges_the_span_s_own_peak():
    class Stats:
        peak_memory_in_bytes = 5_000
        argument_size_in_bytes = 3_000
        output_size_in_bytes = 1_000
        alias_size_in_bytes = 1_000
        temp_size_in_bytes = 2_000
        generated_code_size_in_bytes = 10

    class Compiled:
        calls = 0

        def memory_analysis(self):
            Compiled.calls += 1
            return Stats()
    memory = perf_cache.xla_memory(Compiled(), 6_000)
    assert memory == {"peak": 5_000, "arguments": 3_000, "outputs": 1_000,
                      "aliased": 1_000, "temporaries": 2_000, "code": 10,
                      "limit": 6_000}
    # one analysis serves the span and the fallback's judgement
    assert Compiled.calls == 1
    assert perf_cache._past_peak(memory, memory["peak"]) is None
    assert perf_cache._past_peak(memory, None) is None
    assert "passes" in perf_cache._past_peak(memory, memory["peak"] - 1)
    # a backend with no analysis: nothing on the span, nothing to judge

    class Silent:
        def memory_analysis(self):
            return None
    assert perf_cache.xla_memory(Silent(), 6_000) == {}
    assert perf_cache._past_peak({}, 1) is None


def test_the_fallback_s_memory_wins_where_a_build_fell_back(monkeypatch):
    """The span carries the memory of the executable that will run."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    from gke_ray_train_tpu.perf.cache import StepFallback
    first, second = _small_jit(), jax.jit(lambda x: (x * 2.0).sum())
    peaks = iter([9_000, 4_000])
    monkeypatch.setattr(
        perf_cache, "xla_memory",
        lambda compiled, limit: {"peak": next(peaks), "limit": limit})
    obs_trace.RECORD.clear()
    built = build_or_load_step(
        first, jnp.ones((8, 8), jnp.float32), label="too large",
        attrs={"remat_keep_fallback": False},
        fallback=StepFallback(second, {"remat_keep_fallback": True},
                              peak_limit_bytes=5_000))
    spans = list(obs_trace.RECORD.spans)
    obs_trace.RECORD.clear()
    assert [s["name"] for s in spans].count("step_compile") == 2
    (build,) = [s for s in spans if s["name"] == "step_build"]
    assert build["remat_keep_fallback"] is True
    assert build["xla_memory"]["peak"] == 4_000
    assert built.info["xla_memory"]["peak"] == 4_000
