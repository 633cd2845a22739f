"""The latent-attention routed decoder (GLM-4.7-Flash's layer: a query
latent and a key/value latent, a rotary slice that all heads' keys share,
sigmoid-routed experts beside a shared expert, a leading dense layer)
against the plain reference ``benchmark/reference/glm_mla_moe_decoder.py``
on seeded random weights at a small size with the published structure;
what the new leaves meet on their way: LoRA targets, the host merge,
quantised init, the block checkpoints' names, the refusals.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights as wts
from benchmark import weights_mla as wl
from benchmark.drivers import train_mla as drv
from benchmark.reference import glm_mla_moe_decoder as ref
from gke_ray_train_tpu.data.packing import pack_examples
from gke_ray_train_tpu.models import remat
from gke_ray_train_tpu.models.config import (
    PRESETS, ModelConfig, glm_4_7_flash, preset_for_model_id, tiny)
from gke_ray_train_tpu.models.transformer import (
    DENSE_MLP, SHARED_MLP, _mlp, _moe, block_layout, block_leaves,
    flash_grids, forward, init_params)
from gke_ray_train_tpu.ops import moe

LATENT = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")


def small_config(**over):
    """The published keys at a small size: a dense layer and two sparse
    ones, 4 heads of 24 values without position + 8 rotated and values
    of 32, latents of 128 and 64 (an NF4 group is 64 inputs), 8 router
    outputs of which experts 2-5 are held, 2 a token, one shared."""
    config = {
        "model_type": "glm4_moe_lite", "hidden_act": "silu",
        "attention_bias": False, "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 128, "kv_lora_rank": 64,
        "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 32,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "vocab_size": 96, "max_position_embeddings": 64,
        "moe_intermediate_size": 64, "n_routed_experts": 4,
        "experts_held": [2, 6], "router_outputs": 8,
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
        "norm_topk_prob": True, "routed_scaling_factor": 1.8,
        "partial_rotary_factor": 1, "rope_scaling": None,
        "rope_theta": 1e6, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False}
    config.update(over)
    return config


def packed_batch(rows=2, seq=64, seed=0, vocab=96):
    """Rows packed from several documents, so that document boundaries
    cross the rows."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rows):
        docs = [{"input_ids": rng.integers(1, vocab, n + 1, dtype=np.int32),
                 "loss_weights": np.ones(n + 1, np.float32)}
                for n in (21, 5, 14, 17)]
        out.extend(pack_examples(docs, seq))
    assert len(out) == rows
    return {k: np.stack([r[k] for r in out]) for k in out[0]}


JOB = {"LEARNING_RATE": 1e-3, "WARMUP_RATIO": 0.0, "WEIGHT_DECAY": 0.001,
       "MAX_GRAD_NORM": 0.3, "OPTIM": "adamw",
       "LR_SCHEDULER_TYPE": "cosine"}
STEPS = 3       # the first runs at a rate of 0 (warm-up from nought)


def model_cfg(config, **kw):
    return drv.model_config(config, dtype="float32", param_dtype="float32",
                            attn_impl="xla", max_seq_len=64, **kw)


@pytest.fixture(scope="module")
def trained():
    """Three optimizer steps of the program (the benchmark's seam,
    ``make_train_state``, ``make_train_step``, the job's own optimizer)
    and of the reference, from the same seed, over an NF4 base."""
    from benchmark.drivers.train import optimizer_facts
    from gke_ray_train_tpu.config import (
        optimizer_from_config, schedule_from_config)
    from gke_ray_train_tpu.train import (
        LoraConfig, make_train_state, make_train_step)
    config = small_config()
    cfg = model_cfg(config)
    assert (cfg.prologue_layers, cfg.n_repeats) == (1, 2)
    lora_cfg = LoraConfig(r=4, alpha=8)
    opt = optimizer_from_config(JOB, schedule_from_config(JOB, 10))
    key = wts.seed_key(7)
    params = jax.jit(drv.params_maker(cfg, config, quant_kind="nf4"))(key)
    state = make_train_state(cfg, opt, jax.random.key(1),
                             lora_cfg=lora_cfg, params=params)
    dims = wl.dims_from_config(config)
    lora = {}
    for where, i, first, count, stride, _ in block_layout(cfg):
        lora.setdefault(where, []).append({
            t: {"a": jnp.stack([wl.lora_a(dims, key, t, first + r * stride,
                                          4) for r in range(count)]),
                "b": jnp.zeros((count,) + wl.lora_b_shape(dims, t, 4))}
            for t in state.lora[where][i]})
    state = state._replace(lora=lora)
    step = make_train_step(cfg, opt, lora_cfg=lora_cfg, grad_accum=2,
                           donate=False)
    model, trainer = ref.trainer(
        config, 7, store_dtype="float32", quant_kind="nf4",
        lora={"rank": 4, "alpha": 8, "targets": lora_cfg.targets},
        optimizer=optimizer_facts(JOB, 10), mode="f32")
    out = []
    for s in range(STEPS):
        batch = packed_batch(seed=s)
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in batch.items()})
        out.append(({k: float(v) for k, v in metrics.items()},
                    trainer.step(batch)))
    return cfg, state, trainer, model, out


def test_loss_and_pairs_follow_the_reference(trained):
    _, _, _, model, steps = trained
    for (metrics, reference), pairs in zip(steps, model.held_pairs):
        assert metrics["loss"] == pytest.approx(reference["loss"], rel=2e-5)
        assert metrics["moe_pairs"] == pairs
        assert metrics["moe_pairs_dropped"] == 0
        # 128 positions of which 114 are tokens, 2 picks, 4 of 8 held,
        # two sparse layers: about 230 pairs
        assert 150 < pairs < 320


def test_adapters_follow_the_reference_after_the_steps(trained):
    cfg, state, trainer, _, _ = trained
    seen = 0
    for where, i, first, count, stride, _ in block_layout(cfg):
        for t, ab in state.lora[where][i].items():
            for r in range(count):
                theirs = trainer.lora[first + r * stride][t]
                for k in ("a", "b"):
                    np.testing.assert_allclose(
                        np.asarray(ab[k][r]), np.asarray(theirs[k]),
                        rtol=2e-3, atol=2e-6,
                        err_msg=f"{where}[{i}].{t}.{k} layer "
                                f"{first + r * stride}")
                    seen += 1
    # the five attention matrices in 3 layers, the dense MLP in one,
    # the shared expert in 2
    assert seen == 2 * (3 * 5 + 3 + 2 * 3)


def test_first_gradient_reaches_every_adapted_leaf(trained):
    """Leaf by leaf over all layers, as `correct` compares it."""
    _, _, _, _, steps = trained
    reference = steps[0][1]["grad_norm"]
    assert set(reference) == {f"{t}.{k}" for k in "ab" for t in
                              wl.ATTENTION + wl.DENSE_MLP + wl.SHARED}
    assert all(v > 0 for k, v in reference.items() if k.endswith(".b"))


# ---------------------------------------------------------------------------
# the latent layer by itself
# ---------------------------------------------------------------------------

def one_layer(**over):
    """(config, cfg, params, reference model, batch) of one dense layer
    at latents of 32 and 16, unquantised."""
    config = small_config(num_hidden_layers=1, q_lora_rank=32,
                          kv_lora_rank=16, **over)
    cfg = model_cfg(config)
    params = jax.jit(drv.params_maker(cfg, config, quant_kind=None))(
        wts.seed_key(11))
    model = ref.Model(config, 11, store_dtype="float32", quant_kind=None)
    return config, cfg, params, model, packed_batch(rows=1, seed=3)


def reference_logits(model, batch):
    x = model.outer("embed")[jnp.asarray(batch["inputs"])]
    x, _ = ref.layer_fwd(x, model.layer(0), {}, model.hp,
                         jnp.asarray(batch["positions"]),
                         jnp.asarray(batch["segment_ids"]), "f32")
    x = ref.dd.rms_norm(x, model.outer("final_norm"), 1e-5)
    return np.asarray(x @ model.outer("lm_head"))[0]


def test_latent_attention_against_the_reference(monkeypatch):
    """One layer over a packed row. A departure from the equations (the
    rotary slice left off the keys; the key/value latent not normed)
    gives another result, so the agreement is no accident."""
    _, cfg, params, model, batch = one_layer()
    assert cfg.latent_attention and cfg.resolved_head_dim == 32
    logits = np.asarray(forward(
        params, jnp.asarray(batch["inputs"]), cfg,
        positions=jnp.asarray(batch["positions"]),
        segment_ids=jnp.asarray(batch["segment_ids"])))[0]
    real = np.asarray(batch["segment_ids"][0]) != 0
    np.testing.assert_allclose(logits[real],
                               reference_logits(model, batch)[real],
                               rtol=2e-4, atol=2e-5)
    qkv, norm = ref.latent_qkv, ref.dd.rms_norm

    def no_rotary_on_keys(h, W, lora, hp, positions, mode):
        q, _, v = qkv(h, W, lora, hp, positions, mode)
        _, k, _ = qkv(h, W, lora, hp, jnp.zeros_like(positions), mode)
        return q, k, v

    def no_norm_on_the_kv_latent(x, scale, eps):
        return x if scale.shape == (16,) else norm(x, scale, eps)

    for module, name, planted in (
            (ref, "latent_qkv", no_rotary_on_keys),
            (ref.dd, "rms_norm", no_norm_on_the_kv_latent)):
        with monkeypatch.context() as m:
            m.setattr(module, name, planted)
            other = reference_logits(model, batch)
        assert np.abs(logits[real] - other[real]).max() > 1e-3, name


def test_the_layer_has_the_published_leaves_and_counts():
    cfg = glm_4_7_flash()
    assert cfg.resolved_head_dim == 256 and cfg.rope_dim == 64
    shapes = cfg.attn_leaf_shapes()
    assert shapes == {"wq_a": (2048, 768), "wq_b": (768, 5120),
                      "wkv_a": (2048, 576), "wkv_b": (512, 8960),
                      "wo": (5120, 2048)}
    assert sum(a * b for a, b in shapes.values()) == 21_757_952
    sparse = block_leaves(cfg, 1, "moe")
    assert {"q_latent_norm", "kv_latent_norm"} <= set(sparse)
    assert not {"wq", "wk", "wv", "q_norm", "k_norm"} & set(sparse)
    assert sparse["w_gate"][0] == (1, 64, 2048, 1536)
    # 30B-A3B: every expert of every layer; four of them a token
    assert cfg.param_count() == pytest.approx(29.94e9, rel=1e-3)
    assert cfg.active_param_count() == pytest.approx(3.90e9, rel=1e-3)
    assert preset_for_model_id("zai-org/GLM-4.7-Flash") == cfg
    assert PRESETS["glm-4.7-flash"] is glm_4_7_flash
    with pytest.raises(ValueError, match="together"):
        tiny(q_lora_rank=32)
    with pytest.raises(ValueError, match="one head size"):
        tiny(n_kv_heads=4, q_lora_rank=32, kv_lora_rank=16,
             qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16)
    # a model without latent ranks keeps the digest it was recorded under
    assert "kv_lora_rank" not in tiny().to_dict()
    assert ModelConfig.from_dict(cfg.to_dict()) == cfg


@pytest.mark.parametrize("preset,kind,leaves", [
    ("mistral-7b", "dense",
     ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
      "w_down"]),
    ("k-exaone-236b", "moe",
     ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router", "w_gate",
      "w_up", "w_down", "router_bias", "shared_gate", "shared_up",
      "shared_down", "q_norm", "k_norm"]),
    ("k-exaone-236b", "dense",
     ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
      "w_down", "q_norm", "k_norm"]),
])
def test_the_other_families_keep_their_trees(preset, kind, leaves):
    """Creation order too: the init keys are drawn in it."""
    assert list(block_leaves(PRESETS[preset](), 1, kind)) == leaves


def test_gmm_tiling_comes_from_the_shapes():
    # the routed cell's four products, forward and dx: as before the rule
    for k, n in ((6144, 2048), (2048, 6144)):
        assert moe.gmm_tiling(65536, k, n) == moe.GMM_TILING
    # an expert of 2048 x 1536: tiles that divide, each direction its own
    assert moe.gmm_tiling(32768, 2048, 1536) == (256, 1024, 1536)
    assert moe.gmm_tiling(32768, 1536, 2048) == (256, 768, 2048)
    assert moe.gmm_tiling(64, 48, 200) == (64, 48, 200)


# ---------------------------------------------------------------------------
# one rank's share of the routed layer
# ---------------------------------------------------------------------------

def routed_layer(seed=3, D=32, E=8, F=16):
    k = jax.random.split(jax.random.key(seed), 9)
    W = {"router": jax.random.normal(k[0], (D, E)) * 0.7,
         "router_bias": jax.random.normal(k[1], (E,)) * 0.1,
         "expert_gate": jax.random.normal(k[2], (E, D, F)) * 0.2,
         "expert_up": jax.random.normal(k[3], (E, D, F)) * 0.2,
         "expert_down": jax.random.normal(k[4], (E, F, D)) * 0.2,
         "shared_gate": jax.random.normal(k[5], (D, F)) * 0.2,
         "shared_up": jax.random.normal(k[6], (D, F)) * 0.2,
         "shared_down": jax.random.normal(k[7], (F, D)) * 0.2}
    return W, jax.random.normal(k[8], (2, 24, D))


def share_cfg(held):
    return tiny(d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64,
                n_experts=8, expert_top_k=2, expert_d_ff=16,
                n_shared_experts=1, router="sigmoid", router_bias=True,
                router_scale=1.8, experts_held=held)


def reference_share(x, W, held):
    lo, hi = held
    hp = {"top_k": 2, "held": hi - lo, "held_lo": lo, "routed_scale": 1.8}
    Wh = dict(W, **{n: W[n][lo:hi] for n in wl.EXPERT})
    return ref.em.routed(x, Wh, hp, jnp.ones(x.shape[:-1], bool), "f32")


def test_four_shares_add_up_to_the_uncut_layer():
    """What the four ranks of a 4-way expert-parallel layer compute (each
    its two experts' pairs and the shared expert), with the shared
    expert, which every rank computes alike, counted once, is the
    uncut reference's layer."""
    W, x = routed_layer()
    whole, pairs = reference_share(x, W, (0, 8))
    assert int(pairs) == 2 * 24 * 2
    shared = ref.em.swiglu(x, W["shared_gate"], W["shared_up"],
                           W["shared_down"], None, None, None, 0.0, "f32")
    total = jnp.zeros_like(whole)
    for lo in range(0, 8, 2):
        cfg = share_cfg((lo, lo + 2))
        lp = {"router": W["router"], "router_bias": W["router_bias"],
              "w_gate": W["expert_gate"][lo:lo + 2],
              "w_up": W["expert_up"][lo:lo + 2],
              "w_down": W["expert_down"][lo:lo + 2],
              **{n: W[n] for n in wl.SHARED}}
        y, counters = _moe(x, lp, cfg, jnp.float32, None, None)
        every_rank = _mlp(x, lp, cfg, jnp.float32, which=SHARED_MLP)
        np.testing.assert_allclose(every_rank, shared, rtol=1e-5, atol=1e-6)
        mine, n = reference_share(x, W, (lo, lo + 2))
        np.testing.assert_allclose(y - every_rank, mine, rtol=1e-4,
                                   atol=1e-5)
        assert counters["moe_pairs"] == int(n)
        total = total + (y - every_rank)
    np.testing.assert_allclose(total + shared, whole + shared, rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# the flash kernels at the latent layer's head
# ---------------------------------------------------------------------------

def test_flash_at_heads_of_256_ungrouped_against_the_oracle():
    """20 ungrouped heads of 256 over a packed row, several blocks a
    side, in interpret mode: forward and the three gradients."""
    from gke_ray_train_tpu.ops.attention import (
        dot_product_attention, make_attention_mask)
    from gke_ray_train_tpu.ops.flash_attention import flash_attention
    S, H, dh = 512, 20, 256
    k = jax.random.split(jax.random.key(30), 4)
    q, kk, v = (jax.random.normal(x, (1, S, H, dh)) * 0.3 for x in k[:3])
    row = next(pack_examples(
        [{"input_ids": np.ones(n + 1, np.int32),
          "loss_weights": np.ones(n + 1, np.float32)}
         for n in (200, 90, 150)], S))
    pos = jnp.asarray(row["positions"])[None]
    seg = jnp.asarray(row["segment_ids"])[None]
    real = np.asarray(seg[0] != 0)
    cot = jax.random.normal(k[3], q.shape) * jnp.asarray(real)[None, :, None,
                                                              None]

    def flash(q, kk, v):
        return flash_attention(
            q, kk, v, q_positions=pos, kv_positions=pos, q_segment_ids=seg,
            kv_segment_ids=seg, block_q=128, block_kv=256,
            rows_ordered=True, interpret=True)

    def oracle(q, kk, v):
        mask = make_attention_mask(pos, pos, seg, seg, causal=True)
        return dot_product_attention(q, kk, v, mask)

    got, want = [], []
    for fn, into in ((flash, got), (oracle, want)):
        out, vjp = jax.vjp(fn, q, kk, v)
        into.extend(np.asarray(t) for t in (out, *vjp(cot)))
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(a[:, real], b[:, real], atol=1e-4,
                                   rtol=1e-4, err_msg=name)


def test_blocks_and_grid_of_the_latent_layer():
    """Full causal rows at ungrouped heads of 256 ask for a query block
    of 512 (PERF.md, PR 30's sweep); heads of 128, and grouped heads of
    256 (Gemma-2's: not swept), keep the defaults."""
    from gke_ray_train_tpu.ops import flash_attention as fa
    assert fa.window_blocks(8192, None, 256) == (512, 1024)
    assert fa.window_blocks(8192, None, 256, q_per_kv=2) == (256, 1024)
    assert fa.window_blocks(8192, None, 128) == (256, 1024)
    assert fa.window_blocks(8192, 128, 256) == fa.window_blocks(8192, 128)
    # the full grid (ring, a cache prefill) keeps the defaults
    assert fa.call_plan(8192, 8192, causal=True, window=None,
                        rows_ordered=False, head_dim=256)[:2] == (256, 1024)
    cfg = dataclasses.replace(glm_4_7_flash(), attn_impl="flash")
    grid = flash_grids(cfg, None, 1, 8192)
    # 16 query blocks of 512 over 8 kv blocks of 1024: the causal
    # triangle is 72 of 128 steps a head, 20 heads a row
    assert grid == {"latent": {
        "block_q": 512, "block_kv": 1024, "fwd": [1440, 2560],
        "dq": [1440, 2560], "dkv": [1440, 2560]}}
    assert fa.estimate_vmem_bytes(512, 1024, 256, 2) < 16 * 2**20


# ---------------------------------------------------------------------------
# block checkpoints: the names and their bytes
# ---------------------------------------------------------------------------

def glm_share(**kw):
    return glm_4_7_flash(**{**dict(
        vocab_size=38720, experts_held=(0, 16), n_mtp_layers=0,
        max_seq_len=8192, dtype="bfloat16", param_dtype="bfloat16"), **kw})


def test_chooser_names_and_bytes_of_the_latent_layer():
    """One row of 8192, bf16, 47 layers of which 46 route: the two
    down-projections' outputs are 1,344 values a position, the assembled
    q, k, v 15,360."""
    got = dict(remat.keep_candidates(glm_share(), 1, 8192))
    T = 8192
    assert got == {
        "mlp/gate_up": T * 1 * 2 * 10240 * 2,
        "attn/core": T * 47 * 20 * (256 * 2 + 4),
        "attn/qkv": T * 47 * 15360 * 2,
        "attn/out": T * 47 * 2048 * 2,
        "attn/latent": T * 47 * (768 + 512 + 64) * 2,
        "moe/shared": T * 46 * 2 * 1536 * 2,
        "moe/experts": T * 46 * 2 * 1536 * 2 * 4}
    # every name but a state-space layer's, in the chooser's order
    assert list(got) == [n for n in remat.KEEP_ORDER
                         if not n.startswith("ssm/")]
    # the latents divide over no tensor-parallel axis: every device has
    # them whole
    assert dict(remat.keep_candidates(glm_share(), 1, T, model=4))[
        "attn/latent"] == got["attn/latent"]
    # first-fit keeps the latents where q, k, v (11.8 GB) do not fit
    kept = remat.choose_keep(tuple(got.items()), int(3.0e9))
    assert "attn/latent" in kept and "attn/qkv" not in kept
    # no other family has the name
    from gke_ray_train_tpu.models.config import k_exaone_236b, mistral_7b
    for other in (mistral_7b(), k_exaone_236b()):
        assert "attn/latent" not in dict(
            remat.keep_candidates(other, 1, 1024))


def test_latents_kept_spare_the_down_projections(devices):
    """``attn/latent`` names the down-projections' outputs before the
    norms (a norm's backward reads its input): kept, the two
    down-projections do not run again and the up-projections do;
    ``attn/qkv`` kept as well spares those too."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    from gke_ray_train_tpu.ops.quant import quantize_params
    from gke_ray_train_tpu.train import (
        LoraConfig, make_optimizer, make_train_state, make_train_step)
    cfg = tiny(vocab_size=128, d_model=64, n_layers=2, n_heads=2,
               n_kv_heads=2, d_ff=64, max_seq_len=128, remat=True,
               attn_impl="xla", q_lora_rank=64, kv_lora_rank=64,
               qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32)
    opt = make_optimizer(1e-2)
    lora_cfg = LoraConfig(r=4, alpha=8)
    params = quantize_params(init_params(cfg, jax.random.key(0)), "nf4")
    state = make_train_state(cfg, opt, jax.random.key(1),
                             lora_cfg=lora_cfg, params=params)
    toks = np.random.default_rng(0).integers(1, 128, (4, 128)
                                             ).astype(np.int32)
    batch = {"inputs": jnp.asarray(toks),
             "targets": jnp.asarray(np.roll(toks, -1, 1)),
             "weights": jnp.ones((4, 128), jnp.float32)}

    def recomputed(keep):
        step = make_train_step(cfg, opt, lora_cfg=lora_cfg, grad_accum=2,
                               donate=False, remat_keep=keep)
        table = obs_trace.scope_table(
            step.lower(state, batch).compile().as_text())
        return {obs_trace.scope_path(op) for op in table.values()
                if "rematted_computation" in op
                and op.endswith("dot_general")}

    def bases(paths, stage):
        """How many frozen products run again under ``attn/<stage>``."""
        return sum(p == f"attn/{stage}/base" for p in paths)

    nothing = recomputed(())
    assert {"attn/q_latent/base", "attn/kv_latent/base",
            "attn/out/base"} <= nothing
    # the scope table names a path once: tell the down-projection from
    # the up-projection by what is left when the latents are kept
    latents = recomputed(("attn/latent",))
    assert {"attn/q_latent/base", "attn/kv_latent/base"} <= latents
    both = recomputed(("attn/latent", "attn/qkv"))
    assert not {"attn/q_latent/base", "attn/kv_latent/base"} & both
    assert "attn/out/base" in both
    # with q, k, v alone kept the down-projections still run again
    # (their adapters' gradients read the latents)
    assert bases(recomputed(("attn/qkv",)), "q_latent") == 1


# ---------------------------------------------------------------------------
# adapters, merge, quantised init, refusals
# ---------------------------------------------------------------------------

def latent_tiny(**kw):
    return tiny(**{**dict(
        vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=128, q_lora_rank=64, kv_lora_rank=64, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32, n_experts=8, expert_top_k=2,
        expert_d_ff=64, n_shared_experts=1, n_dense_layers=1,
        router="sigmoid", router_bias=True, router_scale=1.8), **kw})


def test_lora_targets_of_a_latent_layer():
    from gke_ray_train_tpu.train.lora import (
        LoraConfig, _effective_targets, init_lora, lora_specs)
    cfg = latent_tiny()
    default = LoraConfig(r=4)
    assert _effective_targets(cfg, default, "dense") == LATENT + DENSE_MLP[0]
    assert _effective_targets(cfg, default, "moe") == LATENT + SHARED_MLP[0]
    # a job that names q and v alone gets the matrices that make them
    qv = LoraConfig(r=4, targets=("wq", "wv"))
    assert _effective_targets(cfg, qv, "moe") == (
        "wq_a", "wq_b", "wkv_a", "wkv_b")
    # the other families are told what they were told
    plain = tiny()
    assert _effective_targets(plain, default) == default.targets
    lora = init_lora(cfg, default, jax.random.key(0))
    specs = lora_specs(cfg, default)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, lora)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: not isinstance(
                s, (dict, list))))
    assert lora["blocks"][0]["wkv_b"]["a"].shape == (2, 64, 4)
    assert lora["blocks"][0]["wkv_b"]["b"].shape == (2, 4, 4 * (24 + 32))
    assert set(lora["prologue"][0]) == set(LATENT + DENSE_MLP[0])


def test_host_merge_and_quantised_init_take_the_new_leaves():
    from gke_ray_train_tpu.models.qinit import init_quantized_params
    from gke_ray_train_tpu.ops.quant import is_qtensor
    from gke_ray_train_tpu.train.lora import (
        LoraConfig, init_lora, merge_lora)
    cfg = latent_tiny()
    params = init_quantized_params(cfg, jax.random.key(0))
    block = params["blocks"][0]
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "shared_gate",
                 "w_gate"):
        assert is_qtensor(block[name]), name
    assert block["wkv_a"].codes.shape == (2, 64, 64 + 8)
    assert block["wkv_a"].codes.dtype == jnp.uint4
    assert not is_qtensor(block["kv_latent_norm"])
    lora_cfg = LoraConfig(r=4, alpha=8)
    lora = jax.tree.map(lambda x: x + 0.01,
                        init_lora(cfg, lora_cfg, jax.random.key(1)))
    toks = jnp.arange(32).reshape(2, 16) % 128
    want = forward(params, toks, cfg, lora=lora, lora_scale=lora_cfg.scale)
    merged = merge_lora(params, lora, lora_cfg, on_host=True)
    assert not any(is_qtensor(x) for x in jax.tree.leaves(
        merged, is_leaf=is_qtensor))
    got = forward(merged, toks, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_serving_and_a_pipelined_mesh_refuse_by_name(devices):
    from gke_ray_train_tpu.models.kvcache import require_decodable
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    with pytest.raises(NotImplementedError, match="a latent cache"):
        require_decodable(tiny(n_kv_heads=4, q_lora_rank=32,
                               kv_lora_rank=16, qk_nope_head_dim=8,
                               qk_rope_head_dim=8, v_head_dim=16))
    with pytest.raises(NotImplementedError, match="glm-4.7-flash.*latent"):
        require_decodable(glm_4_7_flash())
    cfg = tiny(n_layers=2, n_kv_heads=4, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16)
    mesh = build_mesh(MeshConfig(pipe=2, data=1, fsdp=4), devices)
    with pytest.raises(NotImplementedError, match="no latent attention"):
        forward(init_params(cfg, jax.random.key(0)),
                jnp.zeros((4, 16), jnp.int32), cfg, mesh=mesh)
