"""Continuous-batching serving engine (serve/) on the fake-8 CPU mesh.

The load-bearing contract: iteration-level continuous batching must be
BITWISE-identical to sequential ``greedy_generate_cached`` for the same
request set — including after a mid-batch slot refill — because the
engine's per-slot update rule IS the oracle's loop body. Plus: AOT
decode-sidecar cold start with zero recompiles, quantized-weights
serving, the Ray-actor replica path on the fake-ray harness, and the
checked-in decode-step budget.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gke_ray_train_tpu.models import (
    greedy_generate_cached, init_params, tiny)
from gke_ray_train_tpu.plan import ExecutionPlan
from gke_ray_train_tpu.serve import (
    BatchEngine, Request, form_prompt_buffer, pick_bucket,
    post_train_smoke, prompt_bucket)

EOS = 5


@pytest.fixture(scope="session")
def setup():
    cfg = tiny(vocab_size=97, d_model=32, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32")
    return cfg, init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="session")
def shared_engine(setup):
    """ONE default-plan engine for the tests that only need *an*
    engine (admission checks, truncation, ...): every BatchEngine
    construction costs three executables per bucket, and the suite's
    tier-1 wall is the budget this fixture spends once."""
    cfg, params = setup
    return BatchEngine(params, cfg, plan=_plan(), eos_ids=(EOS,))


@pytest.fixture(scope="session")
def tenant_trees(setup):
    """(LoraConfig, three deterministic NON-identity adapter trees) —
    init_lora starts at identity (b = 0), which would make every
    multi-tenant bitwise check vacuously true; these tenants disagree
    with the base model and with each other."""
    from gke_ray_train_tpu.train.lora import LoraConfig, init_lora
    cfg, _ = setup
    lcfg = LoraConfig(r=2, alpha=4)

    def mk(seed):
        t = init_lora(cfg, lcfg, jax.random.key(seed))
        leaves, td = jax.tree.flatten(t)
        ks = jax.random.split(jax.random.key(seed + 1), len(leaves))
        return jax.tree.unflatten(td, [
            0.05 * jax.random.normal(k, l.shape, l.dtype)
            for k, l in zip(ks, leaves)])

    return lcfg, {f"t{i}": mk(20 + 2 * i) for i in (1, 2, 3)}


def _plan(**kw):
    base = dict(max_batch=3, decode_buckets="128", topology="cpu-8",
                compile_cache=False, aot_train_step=False)
    base.update(kw)
    return ExecutionPlan.from_kwargs(**base)


def _requests(cfg, spec, seed=1):
    """spec = [(prompt_len, max_new), ...] → deterministic requests."""
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}",
                    token_ids=rng.integers(1, cfg.vocab_size,
                                           size=p).astype(np.int32),
                    max_new_tokens=m)
            for i, (p, m) in enumerate(spec)]


def _oracle(params, cfg, req, bucket):
    """Sequential batch-1 greedy decode — the bitwise reference."""
    buf, plen = form_prompt_buffer(req.token_ids, bucket)
    out = greedy_generate_cached(
        params, jnp.asarray(buf), jnp.asarray([plen], jnp.int32), cfg,
        max_new_tokens=req.max_new_tokens, eos_ids=(EOS,))
    return np.asarray(out[0])


# ---------------------------------------------------------------------------
# sequential equivalence
# ---------------------------------------------------------------------------

def test_continuous_matches_sequential_bitwise(setup):
    """Mixed-length request set, more requests than slots: every
    completion's full buffer equals the batch-1 oracle's, bit for bit,
    and finishing slots were refilled without flushing the batch."""
    cfg, params = setup
    eng = BatchEngine(params, cfg, plan=_plan(), eos_ids=(EOS,))
    reqs = _requests(cfg, [(7, 12), (30, 20), (3, 8), (50, 16),
                           (20, 24)])
    comps = eng.run_until_drained(reqs)
    assert [c.rid for c in comps] == [r.rid for r in reqs]
    for r, c in zip(reqs, comps):
        np.testing.assert_array_equal(c.tokens,
                                      _oracle(params, cfg, r, 128))
        assert c.prompt_len == len(r.token_ids)
        assert 0 < c.length - c.prompt_len <= r.max_new_tokens
    # 5 requests through 3 slots: at least two admissions landed in a
    # live batch
    assert eng.refills >= 2
    stats = eng.stats()
    assert stats["completed"] == 5 and stats["pending"] == 0
    assert 0 < stats["batch_occupancy"] <= 1.0
    assert stats["p99_token_latency_s"] >= stats["p50_token_latency_s"]
    assert stats["plan_fingerprint"] == eng.plan.fingerprint()


def test_eos_stops_a_slot(setup):
    """A generated EOS retires the slot with finish_reason='eos' and
    the oracle agrees on the full buffer."""
    cfg, params = setup
    eng = BatchEngine(params, cfg, plan=_plan(max_batch=2),
                      eos_ids=(EOS,))
    # long budgets: some sequence will hit EOS before the length stop
    reqs = _requests(cfg, [(11, 60), (23, 60)], seed=3)
    comps = eng.run_until_drained(reqs)
    for r, c in zip(reqs, comps):
        np.testing.assert_array_equal(c.tokens,
                                      _oracle(params, cfg, r, 128))
    reasons = {c.finish_reason for c in comps}
    assert reasons <= {"eos", "length"}


def test_mid_batch_refill_preserves_survivors(setup):
    """The drilled admission contract: a request admitted into a slot
    freed MID-DECODE must not perturb the surviving sequence — its
    tokens stay bitwise-identical to a batch-1 run."""
    cfg, params = setup
    eng = BatchEngine(params, cfg, plan=_plan(max_batch=2),
                      eos_ids=(EOS,))
    short, long_ = _requests(cfg, [(6, 4), (40, 48)], seed=2)
    eng.submit(short)
    eng.submit(long_)
    # decode until the short request retires while the long one is live
    while eng.completion(short.rid) is None:
        assert eng.step() > 0
    assert eng.completion(long_.rid) is None, \
        "test premise broken: long request finished with the short one"
    refills_before = eng.refills
    late = _requests(cfg, [(17, 10)], seed=9)[0]
    late = dataclasses.replace(late, rid="late")
    eng.submit(late)
    while eng.step() > 0:
        pass
    assert eng.refills > refills_before     # admitted into a live batch
    for req in (short, long_, late):
        np.testing.assert_array_equal(
            eng.completion(req.rid).tokens, _oracle(params, cfg, req, 128))


def test_two_buckets_route_and_match(setup):
    """Requests land in the smallest bucket that fits prompt+new and
    each bucket's outputs match the oracle at that bucket's width."""
    cfg, params = setup
    cfg = dataclasses.replace(cfg, max_seq_len=256)
    eng = BatchEngine(params, cfg, plan=_plan(decode_buckets="128,256"),
                      eos_ids=(EOS,))
    small, big = _requests(cfg, [(20, 16), (150, 24)], seed=4)
    assert eng.submit(small) == 128
    assert eng.submit(big) == 256
    while eng.step() > 0:
        pass
    np.testing.assert_array_equal(eng.completion(small.rid).tokens,
                                  _oracle(params, cfg, small, 128))
    np.testing.assert_array_equal(eng.completion(big.rid).tokens,
                                  _oracle(params, cfg, big, 256))


# ---------------------------------------------------------------------------
# admission contract
# ---------------------------------------------------------------------------

def test_unservable_request_rejected_up_front(shared_engine):
    eng = shared_engine
    with pytest.raises(ValueError, match="largest usable bucket"):
        eng.submit(Request("big", np.arange(1, 10, dtype=np.int32),
                           max_new_tokens=200))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request("empty", np.zeros((0,), np.int32), 8))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request("none", np.arange(1, 5, dtype=np.int32), 0))
    with pytest.raises(ValueError, match="adapter"):
        eng.submit(Request("tenant", np.arange(1, 5, dtype=np.int32), 8,
                           adapter_id="t1"))   # no pool on this engine


def test_overlong_prompt_truncates_loudly(setup, shared_engine, caplog):
    """The reference silently kept the LAST max_prompt tokens; the
    shared bucketing keeps the behavior but logs the drop."""
    cfg, params = setup
    eng = shared_engine
    req = dataclasses.replace(_requests(cfg, [(140, 16)], seed=6)[0],
                              rid="trunc0")
    with caplog.at_level("WARNING"):
        assert eng.submit(req) == 128
    assert any("DROPPED" in r.message for r in caplog.records)
    while eng.step() > 0:
        pass
    trunc = dataclasses.replace(req, token_ids=req.token_ids[-112:])
    np.testing.assert_array_equal(eng.completion(req.rid).tokens,
                                  _oracle(params, cfg, trunc, 128))


def test_generate_answer_warns_on_truncation(setup, caplog):
    """inference.py's comparison path now shares serve/bucketing.py —
    an over-long prompt is truncated with a warning, not silently."""
    from gke_ray_train_tpu.data import ByteTokenizer
    from gke_ray_train_tpu.inference import generate_answer
    cfg, params = setup
    with caplog.at_level("WARNING"):
        out = generate_answer(params, cfg, ByteTokenizer(),
                              "x" * (cfg.max_seq_len + 40),
                              max_new_tokens=16)
    assert isinstance(out, str)
    assert any("DROPPED" in r.message for r in caplog.records)


def test_bucketing_helpers():
    assert prompt_bucket(1) == 128 and prompt_bucket(129) == 256
    assert pick_bucket(10, 20, (128, 256)) == 128
    assert pick_bucket(120, 20, (128, 256)) == 256
    with pytest.raises(ValueError, match="largest usable bucket"):
        pick_bucket(250, 20, (128, 256))
    with pytest.raises(ValueError, match="max_seq_len"):
        pick_bucket(10, 10, (256,), max_seq_len=128)


def test_generate_cache_is_bounded_and_clearable(dp_mesh):
    """The replicated-generate cache must be explicitly releasable —
    it is what used to pin torn-down meshes (and their buffers) for
    the life of the process."""
    from gke_ray_train_tpu import inference
    inference.clear_generate_cache()
    cfg = tiny(vocab_size=64, d_model=32, n_heads=2, n_kv_heads=2)
    f1 = inference._replicated_generate(dp_mesh, cfg, 8, (), 1.0)
    f2 = inference._replicated_generate(dp_mesh, cfg, 8, (), 1.0)
    assert f1 is f2                          # cache hit, no rebuild
    inference._replicated_generate(dp_mesh, cfg, 9, (), 1.0)
    assert len(inference._GENERATE_CACHE) == 2
    assert inference.clear_generate_cache() == 2
    assert not inference._GENERATE_CACHE


# ---------------------------------------------------------------------------
# AOT sidecars: replica cold start without recompiling
# ---------------------------------------------------------------------------

def test_aot_sidecar_cold_start_zero_recompiles(setup, tmp_path):
    """A fresh engine pointed at a warm sidecar dir deserializes every
    executable ('deserialized' provenance, no backend compile of any
    step fn) and produces bitwise-identical tokens — the replica
    cold-start-in-seconds path (same drill as test_perf's train-step
    sidecar)."""
    from gke_ray_train_tpu.analysis.jaxprcheck import RecompileDetector
    cfg, params = setup
    plan = _plan(max_batch=2, aot_train_step=True)
    reqs = _requests(cfg, [(9, 10), (21, 14), (5, 6)], seed=7)

    eng1 = BatchEngine(params, cfg, plan=plan, eos_ids=(EOS,),
                       sidecar_dir=str(tmp_path))
    eng1.warm_up()
    info1 = eng1.executable_info()
    assert {v["source"] for v in info1.values()} == {"compiled"}
    assert len(info1) == 3                   # prefill + decode + insert
    comps1 = eng1.run_until_drained(reqs)

    eng2 = BatchEngine(params, cfg, plan=plan, eos_ids=(EOS,),
                       sidecar_dir=str(tmp_path))
    with RecompileDetector() as det:
        eng2.warm_up()
        comps2 = eng2.run_until_drained([
            dataclasses.replace(r) for r in reqs])
    info2 = eng2.executable_info()
    assert {v["source"] for v in info2.values()} == {"deserialized"}
    assert not det.compiles, (
        f"warm replica start must not compile any step fn; "
        f"compiled: {sorted(det.compiles)}")
    for a, b in zip(comps1, comps2):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    # the decode cost surface stays introspectable for the AOT build
    assert eng1.decode_cost_report() is not None


def test_plan_change_invalidates_serve_sidecar(setup, tmp_path):
    """A sidecar recorded under a different serve shape is stale by
    construction (the AOT key embeds plan.compile_fingerprint())."""
    cfg, params = setup
    e1 = BatchEngine(params, cfg, plan=_plan(aot_train_step=True),
                     eos_ids=(EOS,), sidecar_dir=str(tmp_path))
    e1.warm_up()
    plan2 = _plan(max_batch=2, aot_train_step=True)  # different shape
    e2 = BatchEngine(params, cfg, plan=plan2, eos_ids=(EOS,),
                     sidecar_dir=str(tmp_path))
    e2.warm_up()
    assert {v["source"] for v in e2.executable_info().values()} \
        == {"compiled"}


# ---------------------------------------------------------------------------
# quantized serving
# ---------------------------------------------------------------------------

@pytest.mark.slow  # a full int8 engine build + oracle decode (~10s);
# the fast quantization contract stays in tier-1 via
# test_quantize_for_serving_contract below
def test_quantized_weights_serving_matches_quantized_oracle(setup):
    """serve_quant=int8 quantizes at engine construction; outputs are
    bitwise-identical to the sequential oracle run on the SAME
    quantized tree (quantization changes the model, not the engine)."""
    from gke_ray_train_tpu.ops.quant import quantize_for_serving
    cfg, params = setup
    eng = BatchEngine(params, cfg, plan=_plan(serve_quant="int8"),
                      eos_ids=(EOS,))
    qparams = quantize_for_serving(params, "int8")
    reqs = _requests(cfg, [(12, 10), (33, 12)], seed=8)
    comps = eng.run_until_drained(reqs)
    for r, c in zip(reqs, comps):
        np.testing.assert_array_equal(c.tokens,
                                      _oracle(qparams, cfg, r, 128))


def test_quantize_for_serving_contract(setup):
    from gke_ray_train_tpu.ops.quant import quantize_for_serving
    cfg, params = setup
    assert quantize_for_serving(params, "none") is params
    assert quantize_for_serving(params, None) is params
    with pytest.raises(ValueError, match="serve quant kind"):
        quantize_for_serving(params, "fp4")


# ---------------------------------------------------------------------------
# plan surface
# ---------------------------------------------------------------------------

def test_serve_plan_fields_round_trip_dialects():
    cfg_plan = ExecutionPlan.from_config(
        {"MAX_BATCH": "16", "DECODE_BUCKETS": "512,256",
         "SERVE_QUANT": "INT8"})
    kw_plan = ExecutionPlan.from_kwargs(
        max_batch=16, decode_buckets=[256, 512], serve_quant="int8")
    assert cfg_plan.bucket_list() == (256, 512)
    assert cfg_plan.fingerprint() == kw_plan.fingerprint()
    with pytest.raises(Exception, match="serve_quant"):
        ExecutionPlan.from_kwargs(serve_quant="fp4")
    with pytest.raises(Exception, match="decode_buckets"):
        ExecutionPlan.from_kwargs(decode_buckets="abc")
    with pytest.raises(Exception, match="max_batch"):
        ExecutionPlan.from_kwargs(max_batch=0)


def test_serve_shape_splits_compile_fingerprint():
    a = ExecutionPlan.from_kwargs()
    b = ExecutionPlan.from_kwargs(max_batch=16)
    c = ExecutionPlan.from_kwargs(prefetch=7)   # operational knob
    # serve-shape fields split the SERVE surface (engine sidecars and
    # replica cache dirs stale) ...
    assert a.compile_fingerprint("serve") != b.compile_fingerprint("serve")
    assert a.compile_fingerprint("serve") == c.compile_fingerprint("serve")
    # ... but no longer churn the TRAIN surface (the PR 7 tradeoff,
    # removed by per-surface fingerprints): a serving retune must not
    # invalidate the training job's AOT sidecar
    assert a.compile_fingerprint("train") == b.compile_fingerprint("train")
    assert a.compile_fingerprint("train") == c.compile_fingerprint("train")
    # train-shape fields split train and leave serve alone, symmetric
    d = ExecutionPlan.from_kwargs(grad_accum=2)
    assert a.compile_fingerprint("train") != d.compile_fingerprint("train")
    assert a.compile_fingerprint("serve") == d.compile_fingerprint("serve")
    # mesh fields shape BOTH surfaces
    e = ExecutionPlan.from_kwargs(model=2, fsdp=4, topology="cpu-8")
    assert a.compile_fingerprint("train") != e.compile_fingerprint("train")
    assert a.compile_fingerprint("serve") != e.compile_fingerprint("serve")


def test_flash_prefill_on_mesh_placed_params(setup, fsdp_mesh):
    """The post-train smoke on a multi-chip host hands the engine
    weights that are sharded over the training mesh. The engine reads
    the mesh off the arrays and runs the flash prefill inside a
    shard_map on it (a compiled Mosaic kernel cannot be partitioned by
    GSPMD — the first four-chip compile failed on exactly that), and
    serves the same tokens as with the weights on one device."""
    import dataclasses

    from gke_ray_train_tpu.models import param_specs
    from gke_ray_train_tpu.parallel.sharding import shard_tree
    from gke_ray_train_tpu.serve.engine import params_mesh
    cfg, params = setup
    cfg = dataclasses.replace(cfg, attn_impl="flash")
    placed = shard_tree(params, fsdp_mesh, param_specs(cfg))
    assert params_mesh(params) is None
    assert params_mesh(placed) is fsdp_mesh
    reqs = _requests(cfg, [(9, 6), (40, 5)], seed=3)
    plan = _plan(max_batch=2)
    want = BatchEngine(params, cfg, plan=plan,
                       eos_ids=(EOS,)).run_until_drained(reqs)
    got = BatchEngine(placed, cfg, plan=plan, eos_ids=(EOS,)
                      ).run_until_drained(
        [dataclasses.replace(r) for r in reqs])
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_post_train_smoke_reraises_an_engine_failure(setup, monkeypatch):
    """A failure inside the engine is the job's failure: the smoke used
    to log it and return None, and the job exited 0."""
    cfg, params = setup

    def boom(self, requests=()):
        raise RuntimeError("decode executable failed to compile")

    monkeypatch.setattr(BatchEngine, "run_until_drained", boom)
    with pytest.raises(RuntimeError, match="failed to compile"):
        post_train_smoke(params, cfg, _plan(),
                         [np.arange(1, 9, dtype=np.int32)],
                         eos_ids=(EOS,), max_new_tokens=4)


def test_post_train_smoke_runs_and_skips_loudly(setup, caplog):
    cfg, params = setup
    out = post_train_smoke(
        params, cfg, _plan(),
        [np.arange(1, 20, dtype=np.int32),
         np.arange(1, 9, dtype=np.int32)],
        eos_ids=(EOS,), max_new_tokens=8)
    assert out is not None
    comps, stats = out
    assert len(comps) == 2 and stats["generated_tokens"] > 0
    # no declared bucket fits → loud skip, not a crash
    with caplog.at_level("WARNING"):
        assert post_train_smoke(params, cfg,
                                _plan(decode_buckets="4096"),
                                [np.arange(1, 9, dtype=np.int32)]) is None
    assert any("SERVE_AFTER_TRAIN skipped" in r.message
               for r in caplog.records)


# ---------------------------------------------------------------------------
# decode-step budget (tests/budgets/serve_tiny8.json)
# ---------------------------------------------------------------------------

def test_serve_decode_budget_checked_in():
    """The serving decode step must sit within its checked-in budget
    (any collective in the mesh-local decode = reshard bug; temp/flops
    drift = a cache or attention regression). BUDGET_UPDATE=1
    re-baselines — review the JSON diff like code."""
    from gke_ray_train_tpu.perf.budget import (
        SERVE_PRESETS, assert_within_budget, budget_path,
        build_budget_doc, plan_for_preset, write_budget)
    for name in SERVE_PRESETS:
        doc = build_budget_doc(name)
        path = budget_path(name)
        if os.environ.get("BUDGET_UPDATE") == "1":
            write_budget(doc, path, preset=name)
            continue
        assert os.path.exists(path), (
            f"missing budget {path}; record it: python -m "
            "gke_ray_train_tpu.perf.budget record")
        assert_within_budget(doc, path, plan=plan_for_preset(name))
        assert sum(doc["collective_counts"].values()) == 0


def test_serve_preset_plan_is_pinned_consistently():
    """One fingerprint across the budget JSON, plan_for_preset and
    plancheck's PLAN004 sweep (a stale serve budget fails lint)."""
    from gke_ray_train_tpu.analysis.plancheck import repo_budget_findings
    from gke_ray_train_tpu.perf.budget import (
        SERVE_PRESETS, budget_path, load_budget, plan_for_preset)
    for name in SERVE_PRESETS:
        doc = load_budget(budget_path(name))
        assert doc["_plan_fingerprint"] == \
            plan_for_preset(name).fingerprint()
        assert not [f for f in repo_budget_findings()
                    if f.field == name]


# ---------------------------------------------------------------------------
# multi-tenant serving (ISSUE 17): batched multi-LoRA, adapter cache,
# prefix reuse, speculative decoding
# ---------------------------------------------------------------------------

def _lora_oracle(params, cfg, req, bucket, lora, lora_scale):
    """Batch-1 greedy with ONE adapter — the sequential per-adapter
    reference a mixed-tenant batch must reproduce bitwise."""
    buf, plen = form_prompt_buffer(req.token_ids, bucket)
    out = greedy_generate_cached(
        params, jnp.asarray(buf), jnp.asarray([plen], jnp.int32), cfg,
        max_new_tokens=req.max_new_tokens, eos_ids=(EOS,),
        lora=lora, lora_scale=lora_scale if lora is not None else 1.0)
    return np.asarray(out[0])


def test_mixed_adapter_batch_matches_per_adapter_oracle(setup,
                                                       tenant_trees):
    """The tentpole bitwise drill: one mixed-tenant batch (two LoRA
    tenants + the base model, more requests than slots so refills
    SWITCH the adapter occupying a slot mid-decode) equals the
    sequential per-adapter oracle bit for bit — and the whole run,
    tenant churn included, never leaves the one warmed decode
    executable (RecompileDetector-asserted)."""
    from gke_ray_train_tpu.analysis.jaxprcheck import RecompileDetector
    from gke_ray_train_tpu.serve.adapters import AdapterPool
    cfg, params = setup
    lcfg, trees = tenant_trees
    pool = AdapterPool.from_template(trees["t1"], max_adapters=4)
    for aid in ("t1", "t2"):
        pool.register(aid, trees[aid])
    eng = BatchEngine(params, cfg, plan=_plan(max_batch=2),
                      eos_ids=(EOS,), adapters=pool,
                      lora_scale=lcfg.scale)
    eng.warm_up()
    assert len(eng.executable_info()) == 3   # the engine contract holds
    spec = [(7, 10, "t1"), (25, 12, "t2"), (12, 8, None),
            (9, 10, "t1"), (30, 14, "t2")]
    reqs = [dataclasses.replace(r, adapter_id=a)
            for r, (_, _, a) in zip(
                _requests(cfg, [(p, m) for p, m, _ in spec], seed=31),
                spec)]
    with RecompileDetector() as det:
        comps = eng.run_until_drained(reqs)
    assert not det.findings(), det.findings()
    assert eng.refills >= 2        # slots changed tenants mid-batch
    for r, c in zip(reqs, comps):
        assert c.adapter_id == r.adapter_id
        np.testing.assert_array_equal(
            c.tokens, _lora_oracle(params, cfg, r, 128,
                                   trees.get(r.adapter_id), lcfg.scale))
    stats = eng.stats()
    assert stats["adapter_hits"] == 4 and stats["adapter_misses"] == 0
    assert stats["adapter_evictions"] == 0


def test_zero_adapter_slot_is_bitwise_base_model(setup, tenant_trees):
    """A request WITHOUT an adapter_id on a pooled engine routes to the
    reserved zero slot and must equal the plain no-LoRA oracle exactly
    — adding an exact-zero delta cannot move an argmax."""
    from gke_ray_train_tpu.serve.adapters import AdapterPool
    cfg, params = setup
    lcfg, trees = tenant_trees
    pool = AdapterPool.from_template(trees["t1"], max_adapters=2)
    pool.register("t1", trees["t1"])
    eng = BatchEngine(params, cfg, plan=_plan(max_batch=2),
                      eos_ids=(EOS,), adapters=pool,
                      lora_scale=lcfg.scale)
    req = _requests(cfg, [(14, 10)], seed=33)[0]
    comps = eng.run_until_drained([req])
    np.testing.assert_array_equal(comps[0].tokens,
                                  _oracle(params, cfg, req, 128))


def test_adapter_pool_lru_eviction_and_pinning(setup, tenant_trees):
    """The adapter cache in isolation: loader-backed misses, LRU
    eviction under capacity pressure, pinned slots never evicted, the
    reserved zero slot untouchable, counters exact."""
    from gke_ray_train_tpu.serve.adapters import (
        AdapterPool, AdapterPoolPinned)
    cfg, _ = setup
    _, trees = tenant_trees
    pool = AdapterPool.from_template(trees["t1"], max_adapters=2,
                                     loader=lambda aid: trees[aid])
    assert pool.acquire(None) == 0          # zero slot, never pinned
    s1 = pool.acquire("t1")                 # miss -> loader -> resident
    pool.acquire("t2")                      # miss; pool now full
    pool.release("t1")                      # t1 unpinned, t2 pinned
    s3 = pool.acquire("t3")                 # evicts LRU-unpinned t1
    assert s3 == s1 and "t1" not in pool and "t2" in pool
    st = pool.stats()
    assert st["adapter_misses"] == 3 and st["adapter_evictions"] == 1
    assert st["adapter_resident"] == 2
    pool.acquire("t2")                      # hit
    assert pool.stats()["adapter_hits"] == 1
    with pytest.raises(AdapterPoolPinned):  # t2, t3 both pinned
        pool.acquire("t1")
    with pytest.raises(ValueError, match="immutable"):
        pool.register("t2", trees["t2"])    # ids are immutable


def test_engine_retries_admission_when_pool_pinned(setup, tenant_trees):
    """Eviction under pressure THROUGH the engine: with one tenant slot
    and every slot pinned by an in-flight request, a second tenant's
    request stays pending (no crash) and is admitted — evicting the
    retired tenant — once the slot frees."""
    from gke_ray_train_tpu.serve.adapters import AdapterPool
    cfg, params = setup
    lcfg, trees = tenant_trees
    pool = AdapterPool.from_template(trees["t1"], max_adapters=1,
                                     loader=lambda aid: trees[aid])
    eng = BatchEngine(params, cfg, plan=_plan(max_batch=2),
                      eos_ids=(EOS,), adapters=pool,
                      lora_scale=lcfg.scale)
    r1, r2 = [dataclasses.replace(r, adapter_id=a)
              for r, a in zip(_requests(cfg, [(10, 12), (8, 6)],
                                        seed=35), ("t1", "t2"))]
    eng.submit(r1)
    eng.submit(r2)
    eng.step()                     # r1 admitted+decoding; r2 pinned out
    assert eng.completion(r2.rid) is None
    assert eng.stats()["pending"] == 1
    by_rid = {c.rid: c for c in eng.run_until_drained()}
    assert set(by_rid) == {r1.rid, r2.rid}
    for r in (r1, r2):
        np.testing.assert_array_equal(
            by_rid[r.rid].tokens,
            _lora_oracle(params, cfg, r, 128, trees[r.adapter_id],
                         lcfg.scale))
    st = eng.stats()
    assert st["adapter_evictions"] == 1 and st["adapter_misses"] == 2


def test_prefix_reuse_bitwise_and_counted(setup):
    """Identical prompts prefill ONCE: the reused KV row + first token
    are bitwise what a cold prefill produces (same executable, same
    inputs), so completions match a no-reuse engine exactly; the hit
    counter is exact; the stats key exists only when the feature is
    on."""
    cfg, params = setup
    shared = _requests(cfg, [(18, 10)], seed=37)[0]
    reqs = [dataclasses.replace(shared, rid=f"p{i}") for i in range(3)]
    reqs.append(dataclasses.replace(
        _requests(cfg, [(9, 10)], seed=38)[0], rid="other"))
    cold = BatchEngine(params, cfg, plan=_plan(max_batch=2),
                       eos_ids=(EOS,))
    warm = BatchEngine(params, cfg,
                       plan=_plan(max_batch=2, prefix_cache=True),
                       eos_ids=(EOS,))
    comps_c = cold.run_until_drained(
        [dataclasses.replace(r) for r in reqs])
    comps_w = warm.run_until_drained(reqs)
    for a, b in zip(comps_c, comps_w):
        assert a.rid == b.rid
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert warm.stats()["prefix_hits"] == 2   # 3 identical: 1 cold + 2
    assert "prefix_hits" not in cold.stats()


def test_speculative_self_draft_accept_all_bitwise(setup):
    """SPEC_DRAFT=self: the draft IS the target, so every in-window
    proposal verifies (the accept-all arm) — outputs must be bitwise
    the plain engine's, in ~1/(K+1) the decode iterations, with the
    acceptance ledger counting every accepted token."""
    cfg, params = setup
    reqs = _requests(cfg, [(7, 12), (20, 10), (12, 14)], seed=39)
    plain = BatchEngine(params, cfg, plan=_plan(max_batch=2),
                        eos_ids=(EOS,))
    comps_p = plain.run_until_drained(
        [dataclasses.replace(r) for r in reqs])
    spec = BatchEngine(params, cfg,
                       plan=_plan(max_batch=2, spec_draft="self",
                                  spec_k=3),
                       eos_ids=(EOS,))
    spec.warm_up()
    assert len(spec.executable_info()) == 3  # still ONE fused decode
    comps_s = spec.run_until_drained(reqs)
    for a, b in zip(comps_p, comps_s):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    sp, ss = plain.stats(), spec.stats()
    assert ss["iterations"] < sp["iterations"]
    assert 0 < ss["spec_accepted"] <= ss["spec_proposed"]
    assert "spec_proposed" not in sp


def test_speculative_garbage_draft_still_bitwise(setup):
    """The forced-reject arm: a DISTILLED draft with random weights
    proposes mostly-wrong tokens — the verify step must reject them and
    the output stays bitwise the plain engine's (speculation may only
    ever change HOW FAST tokens appear, never WHICH tokens)."""
    cfg, params = setup
    draft_params = init_params(cfg, jax.random.key(99))
    reqs = _requests(cfg, [(9, 10), (16, 8)], seed=41)
    plain = BatchEngine(params, cfg, plan=_plan(max_batch=2),
                        eos_ids=(EOS,))
    comps_p = plain.run_until_drained(
        [dataclasses.replace(r) for r in reqs])
    spec = BatchEngine(params, cfg,
                       plan=_plan(max_batch=2, spec_draft="distilled",
                                  spec_k=3),
                       eos_ids=(EOS,), draft=(draft_params, cfg))
    comps_s = spec.run_until_drained(reqs)
    for a, b in zip(comps_p, comps_s):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    ss = spec.stats()
    # a random draft agrees with the target only by accident
    assert ss["spec_accepted"] < ss["spec_proposed"]


def test_speculation_composes_with_adapters_bitwise(setup,
                                                   tenant_trees):
    """Speculation + multi-LoRA together: the draft proposes adapter-
    free, the pooled target verifies per-tenant — outputs must still be
    bitwise the (non-speculative) per-adapter oracle's."""
    from gke_ray_train_tpu.serve.adapters import AdapterPool
    cfg, params = setup
    lcfg, trees = tenant_trees
    pool = AdapterPool.from_template(trees["t1"], max_adapters=2)
    pool.register("t1", trees["t1"])
    eng = BatchEngine(params, cfg,
                      plan=_plan(max_batch=2, spec_draft="self",
                                 spec_k=2),
                      eos_ids=(EOS,), adapters=pool,
                      lora_scale=lcfg.scale)
    spec = [("t1", (11, 10)), (None, (19, 8))]
    reqs = [dataclasses.replace(r, adapter_id=a)
            for r, (a, _) in zip(
                _requests(cfg, [s for _, s in spec], seed=43), spec)]
    comps = eng.run_until_drained(reqs)
    for r, c in zip(reqs, comps):
        np.testing.assert_array_equal(
            c.tokens, _lora_oracle(params, cfg, r, 128,
                                   trees.get(r.adapter_id), lcfg.scale))


def test_speculative_headroom_enters_admission(setup, shared_engine,
                                               caplog):
    """Routing budgets prompt + max_new + SPEC_K: the verify window
    must never clamp into an active row's committed history, so a
    prompt that fits a plain engine's bucket EXACTLY is over budget on
    the speculative engine and truncated loudly, with the tightened
    budget named."""
    cfg, params = setup
    # 108 + 20 == 128: fits plain exactly; + spec_k it does not
    req = Request("tight", np.arange(1, 109, dtype=np.int32), 20)
    with caplog.at_level("WARNING"):
        shared_engine.submit(
            dataclasses.replace(req, rid="tight-plain"))
    assert not any("DROPPED" in r.message for r in caplog.records)
    while shared_engine.step() > 0:   # don't leak a pending request
        pass                          # into later shared-engine tests
    caplog.clear()
    spec = BatchEngine(params, cfg,
                       plan=_plan(max_batch=2, spec_draft="self",
                                  spec_k=4),
                       eos_ids=(EOS,))
    with caplog.at_level("WARNING"):
        spec.submit(req)              # routing only — no compile
    assert any("104-token budget" in r.message
               for r in caplog.records)


def test_multitenant_plan_knobs_three_dialects_and_surfaces():
    """MAX_ADAPTERS / PREFIX_CACHE / SPEC_DRAFT / SPEC_K land
    identically from kwargs and config dialects, validate loudly, and
    split ONLY the serve compile surface (a serving retune must not
    stale the training sidecar)."""
    cfg_plan = ExecutionPlan.from_config(
        {"MAX_ADAPTERS": "4", "PREFIX_CACHE": "1",
         "SPEC_DRAFT": "SELF", "SPEC_K": "3"})
    kw_plan = ExecutionPlan.from_kwargs(
        max_adapters=4, prefix_cache=True, spec_draft="self", spec_k=3)
    assert cfg_plan.fingerprint() == kw_plan.fingerprint()
    assert ExecutionPlan.from_config(
        {"SPEC_DRAFT": "off"}).spec_draft == "none"
    with pytest.raises(Exception, match="spec_draft"):
        ExecutionPlan.from_kwargs(spec_draft="oracle")
    with pytest.raises(Exception, match="max_adapters"):
        ExecutionPlan.from_kwargs(max_adapters=0)
    with pytest.raises(Exception, match="spec_k"):
        ExecutionPlan.from_kwargs(spec_draft="self", spec_k=0)
    base = ExecutionPlan.from_kwargs()
    for kw in (dict(max_adapters=4), dict(prefix_cache=True),
               dict(spec_draft="self"), dict(spec_k=8)):
        p = ExecutionPlan.from_kwargs(**kw)
        assert p.compile_fingerprint("serve") \
            != base.compile_fingerprint("serve"), kw
        assert p.compile_fingerprint("train") \
            == base.compile_fingerprint("train"), kw


def test_post_train_smoke_serves_tagged_adapters(setup, tenant_trees):
    """Satellite: the SERVE_AFTER_TRAIN smoke with adapter_id tags
    routes tagged prompts through a real AdapterPool (the batched
    multi-tenant path end to end) and reports the tenant traffic."""
    cfg, params = setup
    lcfg, trees = tenant_trees
    out = post_train_smoke(
        params, cfg, _plan(max_batch=2),
        [np.arange(1, 20, dtype=np.int32),
         np.arange(1, 9, dtype=np.int32)],
        eos_ids=(EOS,), max_new_tokens=6,
        lora=trees["t1"], lora_scale=lcfg.scale,
        adapter_ids=["tuned", None])
    assert out is not None
    comps, stats = out
    assert [c.adapter_id for c in comps] == ["tuned", None]
    assert stats["adapter_requests"] == 1
    assert stats["generated_tokens"] > 0
    # the tagged completion really decoded THROUGH the adapter
    req = Request("o", np.arange(1, 20, dtype=np.int32), 6)
    np.testing.assert_array_equal(
        comps[0].tokens,
        _lora_oracle(params, cfg, req, 128, trees["t1"], lcfg.scale))


# ---------------------------------------------------------------------------
# Ray-actor replica deployment (fake-ray harness)
# ---------------------------------------------------------------------------

def _factory(cfg, params, plan):
    def build():
        return BatchEngine(params, cfg, plan=plan, eos_ids=(EOS,))
    return build


def _payload(reqs):
    return [{"rid": r.rid, "token_ids": r.token_ids.tolist(),
             "max_new_tokens": r.max_new_tokens} for r in reqs]


@pytest.fixture
def fake_ray_serving(monkeypatch):
    import sys

    from test_rayint_cluster import make_fake_ray

    import gke_ray_train_tpu.rayint.serving as serving_mod
    record = {"actor_opts": [], "placement_groups": [], "actors": [],
              "sched_bundles": [], "removed_pgs": [], "killed": []}
    ray, mods = make_fake_ray(record)
    monkeypatch.setattr(serving_mod, "ray", ray)
    monkeypatch.setattr(serving_mod, "_HAS_RAY", True)
    for name, mod in mods.items():
        monkeypatch.setitem(sys.modules, name, mod)
    monkeypatch.setitem(sys.modules, "ray", ray)
    return record


def test_ray_replica_deployment_smoke(setup, fake_ray_serving):
    """The actor path end to end on the fake-ray harness: replicas
    built as actors, requests scattered round-robin, completions
    bitwise-equal to the oracle, heartbeats flowing to the Supervisor
    actor, teardown kills every replica."""
    from gke_ray_train_tpu.rayint.serving import ServeDeployment
    from gke_ray_train_tpu.rayint.supervisor import Supervisor
    cfg, params = setup
    dep = ServeDeployment(_factory(cfg, params, _plan(max_batch=2)),
                          num_replicas=2, use_ray=True)
    infos = dep.start()
    assert len(infos) == 2
    reqs = _requests(cfg, [(10, 8), (25, 10), (6, 6)], seed=11)
    payloads = dep.serve(_payload(reqs))
    assert [p["rid"] for p in payloads] == [r.rid for r in reqs]
    for r, p in zip(reqs, payloads):
        np.testing.assert_array_equal(np.asarray(p["tokens"], np.int32),
                                      _oracle(params, cfg, r, 128))
        assert p["finish_reason"] in ("eos", "length")
    # health: every replica beat the supervisor board; nothing stalled
    sups = [a for a in fake_ray_serving["actors"]
            if isinstance(a, Supervisor)]
    assert len(sups) == 1
    snap = sups[0].snapshot()
    assert set(snap) == {0, 1} and all(v["step"] > 0
                                       for v in snap.values())
    assert dep.stalled(1e6) == []
    stats = dep.stats()
    assert len(stats) == 2 and all(s["completed"] >= 1 for s in stats)
    dep.shutdown()
    assert len(fake_ray_serving["killed"]) == 3   # 2 replicas + supervisor


def test_local_deployment_path(setup):
    """use_ray=False degrades to in-process replicas on a
    HeartbeatBoard — the no-cluster path."""
    from gke_ray_train_tpu.rayint.serving import ServeDeployment
    cfg, params = setup
    dep = ServeDeployment(_factory(cfg, params, _plan(max_batch=2)),
                          num_replicas=2, use_ray=False)
    dep.start()
    reqs = _requests(cfg, [(8, 6), (19, 8)], seed=12)
    payloads = dep.serve(_payload(reqs))
    for r, p in zip(reqs, payloads):
        np.testing.assert_array_equal(np.asarray(p["tokens"], np.int32),
                                      _oracle(params, cfg, r, 128))
    assert dep.stalled(1e6) == []
    dep.shutdown()
