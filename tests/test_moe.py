"""MoE / expert parallelism (ops/moe.py, SURVEY.md §2c row EP).

Oracles:
- E=1 top-1 with ample capacity == the dense MLP with that expert's
  weights (the dispatch machinery collapses to identity).
- A per-token python-loop oracle for real routing (top-2, renormalized
  gates, capacity drops).
- Sharded forward over the tp mesh (experts over `model`) matches the
  unsharded forward — the GSPMD-EP equivalence check.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from gke_ray_train_tpu.analysis.kernelcheck import _sub_jaxprs
from gke_ray_train_tpu.models import init_params, mixtral_8x7b
from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.transformer import forward, param_specs
from gke_ray_train_tpu.ops.moe import expert_capacity, moe_mlp
from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
from gke_ray_train_tpu.parallel.sharding import shard_tree
from gke_ray_train_tpu.train import (
    LoraConfig, make_optimizer, make_train_state, make_train_step,
    warmup_cosine_schedule)
from gke_ray_train_tpu.train.lora import init_lora


def moe_cfg(**kw):
    base = dict(name="moe-tiny", d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=64, vocab_size=128, max_seq_len=64,
                n_experts=4, expert_top_k=2, capacity_factor=2.0,
                dtype="float32", param_dtype="float32", attn_impl="xla",
                remat=False)
    base.update(kw)
    return ModelConfig(**base)


def rand_moe_weights(cfg, seed=0):
    rng = np.random.default_rng(seed)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts

    def w(*shape):
        return jnp.asarray(rng.normal(0, 0.05, shape), jnp.float32)
    return w(D, E), w(E, D, F), w(E, D, F), w(E, F, D)


def naive_moe(x, router_w, w_gate, w_up, w_down, cfg):
    """Per-token loop oracle: same top-k, renorm, and per-(row, expert)
    capacity counting as the einsum dispatch."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.expert_top_k
    C = expert_capacity(cfg, S)
    probs = jax.nn.softmax(
        np.asarray(x, np.float64) @ np.asarray(router_w, np.float64), -1)
    probs = np.asarray(probs)
    y = np.zeros((B, S, D))
    for b in range(B):
        counts = np.zeros(E, int)
        # slot-0 choices take capacity before any slot-1 choice
        # (matching the dispatch loop's per-k cumsum ordering)
        picks = []  # (k, s, e, gate)
        for s in range(S):
            top = np.argsort(-probs[b, s])[:K]
            renorm = probs[b, s, top] / probs[b, s, top].sum()
            for k in range(K):
                picks.append((k, s, top[k], renorm[k]))
        for k, s, e, g in sorted(picks, key=lambda t: (t[0], t[1])):
            if counts[e] >= C:
                continue
            counts[e] += 1
            xe = np.asarray(x[b, s], np.float64)
            gate = xe @ np.asarray(w_gate[e], np.float64)
            up = xe @ np.asarray(w_up[e], np.float64)
            act = gate / (1 + np.exp(-gate))  # silu
            y[b, s] += g * ((act * up) @ np.asarray(w_down[e], np.float64))
    return y


def test_single_expert_equals_dense_mlp():
    """E=1, K=1, capacity >= S: routing is a no-op and the MoE layer must
    equal x @ w_gate/silu/up/down with the single expert's weights."""
    from gke_ray_train_tpu.models.transformer import _mlp
    cfg = moe_cfg(n_experts=1, expert_top_k=1, capacity_factor=4.0)
    router_w, w_gate, w_up, w_down = rand_moe_weights(cfg, seed=1)
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (2, 16, 32)),
                    jnp.float32)
    y, aux = moe_mlp(x, router_w, w_gate, w_up, w_down, cfg, jnp.float32)
    dense = _mlp(x, {"w_gate": w_gate[0], "w_up": w_up[0],
                     "w_down": w_down[0]}, cfg, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    # single expert gets every token: perfectly "balanced" by definition
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)


def test_moe_matches_naive_loop():
    cfg = moe_cfg()
    router_w, w_gate, w_up, w_down = rand_moe_weights(cfg, seed=3)
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (2, 24, 32)),
                    jnp.float32)
    y, aux = moe_mlp(x, router_w, w_gate, w_up, w_down, cfg, jnp.float32)
    ref = naive_moe(x, router_w, w_gate, w_up, w_down, cfg)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)
    assert 0.5 < float(aux) < float(cfg.n_experts)


def test_capacity_drops_are_graceful():
    """Tiny capacity: overflow tokens fall back toward the residual path
    (partial or zero MLP output), never NaN."""
    cfg = moe_cfg(capacity_factor=0.25)
    router_w, w_gate, w_up, w_down = rand_moe_weights(cfg, seed=5)
    x = jnp.asarray(np.random.default_rng(6).normal(0, 1, (1, 32, 32)),
                    jnp.float32)
    y, aux = moe_mlp(x, router_w, w_gate, w_up, w_down, cfg, jnp.float32)
    assert np.all(np.isfinite(np.asarray(y)))
    ref = naive_moe(x, router_w, w_gate, w_up, w_down, cfg)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)


def test_moe_aux_ignores_padded_tokens():
    """Weighted router aux (ADVICE r4): appending padded (weight-0)
    positions must leave the aux unchanged — the router is pressured to
    balance real tokens, not padding."""
    cfg = moe_cfg()
    router_w, w_gate, w_up, w_down = rand_moe_weights(cfg, seed=9)
    rng = np.random.default_rng(10)
    real = jnp.asarray(rng.normal(0, 1, (2, 16, 32)), jnp.float32)
    _, aux_real = moe_mlp(real, router_w, w_gate, w_up, w_down, cfg,
                          jnp.float32,
                          weights=jnp.ones((2, 16), jnp.float32))
    # pad to twice the length with weight-0 junk that routes elsewhere
    junk = jnp.asarray(rng.normal(3, 1, (2, 16, 32)), jnp.float32)
    padded = jnp.concatenate([real, junk], axis=1)
    w = jnp.concatenate([jnp.ones((2, 16)), jnp.zeros((2, 16))],
                        axis=1).astype(jnp.float32)
    _, aux_pad = moe_mlp(padded, router_w, w_gate, w_up, w_down, cfg,
                         jnp.float32, weights=w)
    np.testing.assert_allclose(float(aux_pad), float(aux_real),
                               rtol=1e-5)
    # unweighted aux over the padded batch DOES differ — the masked
    # version is measuring something real
    _, aux_unw = moe_mlp(padded, router_w, w_gate, w_up, w_down, cfg,
                         jnp.float32)
    assert abs(float(aux_unw) - float(aux_real)) > 1e-4
    # all-zero weights (pipeline garbage ticks): aux must be exactly 0
    _, aux_zero = moe_mlp(real, router_w, w_gate, w_up, w_down, cfg,
                          jnp.float32,
                          weights=jnp.zeros((2, 16), jnp.float32))
    assert float(aux_zero) == 0.0


def _all_eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn.params):
            yield from _all_eqns(sub)
        yield eqn


def test_moe_bf16_combine_stored_in_compute_dtype():
    """The [B,S,E,C] combine/dispatch tensors are stored in the compute
    dtype (VERDICT r4 weak #4 memory fix), every product accumulates in
    float32 and rounds once on the way out. Read off the jaxpr: XLA:CPU
    cannot execute a BF16 x BF16 = F32 dot at all, so nothing of the
    expert path runs here and its closeness to the fp32 path is the
    chip's to show (PERF.md §7)."""
    cfg = moe_cfg()
    B, S, E, C = 2, 16, cfg.n_experts, expert_capacity(cfg, 16)
    router_w, w_gate, w_up, w_down = rand_moe_weights(cfg, seed=11)
    x = jnp.asarray(np.random.default_rng(12).normal(0, 1, (B, S, 32)),
                    jnp.float32)
    args = (x, router_w, w_gate, w_up, w_down)
    closed = jax.make_jaxpr(
        lambda *a: moe_mlp(*a, cfg, jnp.bfloat16))(*args)
    y16, aux16 = closed.out_avals
    assert y16.dtype == jnp.bfloat16 and y16.shape == x.shape
    assert aux16.dtype == jnp.float32
    dots = [e for e in _all_eqns(closed.jaxpr)
            if e.primitive.name == "dot_general"]
    assert len(dots) == 6      # router, dispatch, gate, up, down, combine
    for e in dots:
        assert e.params["preferred_element_type"] == jnp.float32, e
    # the two big operands: dispatch into the first expert product,
    # combine into the last
    big = [v.aval for e in dots for v in e.invars
           if v.aval.shape == (B, S, E, C)]
    assert len(big) == 2 and all(a.dtype == jnp.bfloat16 for a in big)
    # ...and every expert-side operand is bfloat16 (the router's stay
    # float32)
    assert [{str(v.aval.dtype) for v in e.invars} for e in dots] == \
        [{"float32"}] + [{"bfloat16"}] * 5
    # aux is router-side fp32 math either way; with y unused the bf16
    # products are dead code, so this much does execute
    aux16 = jax.jit(lambda *a: moe_mlp(*a, cfg, jnp.bfloat16)[1])(*args)
    _, aux32 = moe_mlp(*args, cfg, jnp.float32)
    np.testing.assert_allclose(float(aux16), float(aux32), rtol=1e-5)


def test_moe_forward_sharded_matches_unsharded():
    """Experts sharded over `model` (EP): same logits as unsharded."""
    cfg = moe_cfg(attn_impl="xla")
    params = init_params(cfg, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, cfg.vocab_size, (4, 16)),
        jnp.int32)
    ref = forward(params, tokens, cfg)
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2, context=1))
    sharded = shard_tree(params, mesh, param_specs(cfg))
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(
        sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_with_ring_attention_matches_unsharded(tp_mesh):
    """MoE x ring attention in ONE forward: the routed expert MLP and
    the ppermute K/V ring share the context-sharded activations — the
    one composition cell the per-sublayer tests don't reach together."""
    cfg = moe_cfg(attn_impl="ring")
    params = init_params(cfg, jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, cfg.vocab_size, (4, 32)),
        jnp.int32)
    ref = forward(params, tokens,
                  dataclasses.replace(cfg, attn_impl="xla"))
    sharded = shard_tree(params, tp_mesh, param_specs(cfg))
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh=tp_mesh))(
        sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_forward_context_sharded_matches_unsharded():
    """MoE x CP: the dispatch cumsum runs over a context-SHARDED
    sequence axis (GSPMD associative-scan collectives) — logits must
    still be exact."""
    cfg = moe_cfg(attn_impl="xla")
    params = init_params(cfg, jax.random.key(8))
    tokens = jnp.asarray(
        np.random.default_rng(15).integers(0, cfg.vocab_size, (4, 16)),
        jnp.int32)
    ref = forward(params, tokens, cfg)
    mesh = build_mesh(MeshConfig(data=2, fsdp=1, model=2, context=2))
    sharded = shard_tree(params, mesh, param_specs(cfg))
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(
        sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_train_step_aux_and_updates(fsdp_mesh):
    """Full jitted train step on an MoE model: finite loss, router and
    every expert receive gradient updates, aux term reported."""
    cfg = moe_cfg(remat=True)
    # constant lr: warmup schedules give ~0 lr at step 0, which would
    # make the "params moved" assertions vacuous
    schedule = (lambda step: 1e-2)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=fsdp_mesh)
    step = make_train_step(cfg, opt, mesh=fsdp_mesh, grad_accum=2,
                           schedule=schedule, donate=False)
    rng = np.random.default_rng(8)
    batch = {
        "inputs": jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32),
        "weights": jnp.ones((8, 32), jnp.float32),
    }
    state2, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    r0 = np.asarray(state.params["blocks"][0]["router"])
    r1 = np.asarray(state2.params["blocks"][0]["router"])
    assert not np.allclose(r0, r1), "router got no update"
    g0 = np.asarray(state.params["blocks"][0]["w_gate"])
    g1 = np.asarray(state2.params["blocks"][0]["w_gate"])
    per_expert_delta = np.abs(g1 - g0).reshape(g0.shape[0], g0.shape[1], -1
                                               ).sum(axis=(0, 2))
    assert np.all(per_expert_delta > 0), (
        f"some experts got no gradient: {per_expert_delta}")


def test_moe_qlora_attention_adapters(fsdp_mesh):
    """QLoRA on an MoE model: quantized expert bank + attention-only
    adapters (MLP targets are filtered out)."""
    from gke_ray_train_tpu.models.qinit import init_quantized_params
    cfg = moe_cfg(remat=True)
    lcfg = LoraConfig(r=4, alpha=8)
    lora = init_lora(cfg, lcfg, jax.random.key(1))
    assert set(lora["blocks"][0]) == {"wq", "wk", "wv", "wo"}

    params = init_quantized_params(cfg, jax.random.key(0), kind="nf4")
    from gke_ray_train_tpu.ops.quant import is_qtensor
    assert is_qtensor(params["blocks"][0]["w_gate"])

    schedule = warmup_cosine_schedule(1e-3, 100)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(2), params=params,
                             lora_cfg=lcfg)
    step = make_train_step(cfg, opt, lora_cfg=lcfg, schedule=schedule,
                           donate=False)
    rng = np.random.default_rng(9)
    batch = {
        "inputs": jnp.asarray(rng.integers(0, 128, (4, 32)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, 128, (4, 32)), jnp.int32),
        "weights": jnp.ones((4, 32), jnp.float32),
    }
    state2, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_moe_decode_kvcache():
    """Greedy KV-cache decode through the MoE block (S=1 steps)."""
    from gke_ray_train_tpu.models import greedy_generate_cached
    cfg = moe_cfg(remat=False)
    params = init_params(cfg, jax.random.key(3))
    B, Lp, new = 1, 8, 4
    prompt = jnp.zeros((B, Lp + new), jnp.int32).at[:, :Lp].set(
        jax.random.randint(jax.random.key(4), (B, Lp), 1, cfg.vocab_size))
    lens = jnp.full((B,), Lp, jnp.int32)
    out = greedy_generate_cached(params, prompt, lens, cfg,
                                 max_new_tokens=new)
    assert out.shape == (B, Lp + new)


def test_moe_active_param_count():
    cfg = mixtral_8x7b()
    total, active = cfg.param_count(), cfg.active_param_count()
    assert 45e9 < total < 50e9, total          # ~47B
    assert 12e9 < active < 14e9, active        # ~13B
    dense = dataclasses.replace(cfg, n_experts=0)
    assert dense.param_count() == dense.active_param_count()


def test_moe_hf_roundtrip(tmp_path):
    """Mixtral-layout HF export/import: save → load reproduces logits;
    the quantized streaming load runs and shrinks the expert bank."""
    from gke_ray_train_tpu.ckpt import load_hf_checkpoint, save_hf_checkpoint
    from gke_ray_train_tpu.ops.quant import is_qtensor

    cfg = moe_cfg()
    params = init_params(cfg, jax.random.key(5))
    out = str(tmp_path / "mixtral_tiny")
    save_hf_checkpoint(params, cfg, out, dtype="float32")

    import json
    import os
    with open(os.path.join(out, "config.json")) as f:
        hf_cfg = json.load(f)
    assert hf_cfg["num_local_experts"] == cfg.n_experts
    assert hf_cfg["num_experts_per_tok"] == cfg.expert_top_k

    loaded = load_hf_checkpoint(out, cfg)
    tokens = jnp.asarray(
        np.random.default_rng(11).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32)
    np.testing.assert_allclose(
        np.asarray(forward(loaded, tokens, cfg)),
        np.asarray(forward(params, tokens, cfg)), rtol=2e-4, atol=2e-4)

    qloaded = load_hf_checkpoint(out, cfg, quantize="nf4")
    assert is_qtensor(qloaded["blocks"][0]["w_gate"])
    assert qloaded["blocks"][0]["w_gate"].codes.shape[:2] == (
        cfg.n_repeats, cfg.n_experts)
    logits = forward(qloaded, tokens, cfg)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    # EP-mesh load: model>1 shards the expert dim of the [R, E, D, F]
    # bank — the streamed [1, 1, D, F] slices must be placed with their
    # own (lead-dims-unsharded) sharding, not the full leaf's (r4 review
    # finding: this crashed with 'cannot split size-1 dim')
    ep_mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2, context=1))
    ep_loaded = load_hf_checkpoint(out, cfg, mesh=ep_mesh)
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh=ep_mesh))(
        ep_loaded, tokens)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(forward(params, tokens, cfg)),
                               rtol=2e-4, atol=2e-4)


def test_moe_pipeline_forward_matches_plain():
    """MoE blocks through the pipelined path (vmapped stage dim):
    logits are EXACT vs the plain path — dispatch capacity is per
    sequence row, so routing within a microbatch is unchanged."""
    cfg = moe_cfg()
    params = init_params(cfg, jax.random.key(0))
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=1, context=1,
                                 pipe=2))
    tokens = jnp.asarray(
        np.random.default_rng(13).integers(0, cfg.vocab_size, (8, 16)),
        jnp.int32)
    ref = forward(params, tokens, cfg)
    sharded = shard_tree(params, mesh, param_specs(cfg))
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(
        sharded, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_pipeline_train_step():
    """PP x MoE train step: finite loss, router updated, and the aux
    term excludes warmup/drain garbage passes (it stays in the same
    ballpark as the plain path's aux)."""
    cfg = moe_cfg(remat=True)
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=1, context=1,
                                 pipe=2))
    schedule = (lambda step: 1e-2)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, schedule=schedule,
                           donate=False, pipe_microbatches=2)
    rng = np.random.default_rng(14)
    batch = {
        "inputs": jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32),
        "weights": jnp.ones((8, 32), jnp.float32),
    }
    state2, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    r0 = np.asarray(state.params["blocks"][0]["router"])
    r1 = np.asarray(state2.params["blocks"][0]["router"])
    assert not np.allclose(r0, r1)

    # the pipelined aux itself: warmup/drain masking + /M /n_layers
    # scaling must land near the plain path's joint-batch statistic
    # (mean-of-microbatch-means vs joint mean differ only by the
    # cross-microbatch covariance)
    from gke_ray_train_tpu.models.transformer import forward as fwd
    _, aux_pp = jax.jit(
        lambda p, t: fwd(p, t, cfg, mesh=mesh, with_aux=True))(
        state.params, batch["inputs"])
    _, aux_plain = fwd(jax.device_get(state.params), batch["inputs"],
                       cfg, with_aux=True)
    np.testing.assert_allclose(float(aux_pp["router_aux"]),
                               float(aux_plain["router_aux"]), rtol=1e-2)

    # plain-mesh reference loss with aux_coef=0 must match the PP loss
    # with aux_coef=0 exactly (logits identical; only aux may differ)
    cfg0 = dataclasses.replace(cfg, router_aux_coef=0.0)
    plain = build_mesh(MeshConfig(data=2, fsdp=4, model=1, context=1))
    s_ref = make_train_state(cfg0, opt, jax.random.key(0), mesh=plain)
    st_ref = make_train_step(cfg0, opt, mesh=plain, schedule=schedule,
                             donate=False)
    _, m_ref = st_ref(s_ref, batch)
    s_pp = make_train_state(cfg0, opt, jax.random.key(0), mesh=mesh)
    st_pp = make_train_step(cfg0, opt, mesh=mesh, schedule=schedule,
                            donate=False, pipe_microbatches=2)
    _, m_pp = st_pp(s_pp, batch)
    np.testing.assert_allclose(float(m_pp["loss"]), float(m_ref["loss"]),
                               rtol=1e-5)
