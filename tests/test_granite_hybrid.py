"""The hybrid state-space / attention routed decoder (Granite-4.0-H-Small's
layers: Mamba-2 mixers beside attention without positions, softmax-routed
experts beside a shared expert of its own width, four multipliers)
against the plain reference ``benchmark/reference/granite_hybrid_decoder.py``
on seeded random weights at a small size with the published structure;
the chunked scan against the recurrence; a packed row against its
documents run alone; the shares of an expert-parallel layer; what the
new leaves meet on their way: LoRA targets, quantised init, the block
checkpoints' names, the scopes, the refusals.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness as hs
from benchmark import weights as wts
from benchmark import weights_ssm as ws
from benchmark.drivers import train_ssm as drv
from benchmark.reference import granite_hybrid_decoder as ref
from gke_ray_train_tpu.data.packing import pack_examples
from gke_ray_train_tpu.models import remat
from gke_ray_train_tpu.models.config import (
    PRESETS, ModelConfig, granite_4_0_h_small, preset_for_model_id, tiny)
from gke_ray_train_tpu.models.transformer import (
    SHARED_MLP, _mlp, _moe, block_layout, block_leaves, forward,
    init_params, ssm_geometry)
from gke_ray_train_tpu.ops import moe, ssm

MIXER_LEAVES = ["in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
                "ssm_norm", "out_proj"]


def small_config(**over):
    """The published keys at a small size: one period of four layers
    (three mixers around an attention layer), 4 heads of 32 in the
    mixer with a state of 8 and chunks of 8 (documents begin inside
    chunks), 4 / 2 attention heads of 16, 8 router outputs of which
    experts 2-5 are held, 3 a token, a shared expert of 128 beside
    experts of 64 (an NF4 group is 64 inputs)."""
    config = {
        "model_type": "granitemoehybrid", "hidden_act": "silu",
        "attention_bias": False, "attention_multiplier": 0.0625,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "logits_scaling": 16, "hidden_size": 64, "intermediate_size": 64,
        "shared_intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "layer_types": ["mamba", "mamba", "attention", "mamba"],
        "num_hidden_layers": 4, "mamba_n_heads": 4, "mamba_d_head": 32,
        "mamba_d_state": 8, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "normalization_function": "rmsnorm",
        "position_embedding_type": "nope", "vocab_size": 96,
        "max_position_embeddings": 64, "num_local_experts": 4,
        "experts_held": [2, 6], "router_outputs": 8,
        "num_experts_per_tok": 3, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "rope_scaling": None,
        "tie_word_embeddings": True}
    config.update(over)
    return config


def packed_batch(rows=2, seq=64, seed=0, vocab=96, lengths=(21, 5, 14, 17)):
    """Rows packed from several documents: boundaries at 22, 28 and 43,
    none of them a multiple of the chunk."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rows):
        docs = [{"input_ids": rng.integers(1, vocab, n + 1, dtype=np.int32),
                 "loss_weights": np.ones(n + 1, np.float32)}
                for n in lengths]
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
    assert cfg.block_pattern == ("ssm", "ssm", "global", "ssm")
    lora_cfg = LoraConfig(r=4, alpha=8)
    opt = optimizer_from_config(JOB, schedule_from_config(JOB, 10))
    key = wts.seed_key(7)
    params = jax.jit(drv.params_maker(cfg, config, quant_kind="nf4"))(key)
    state = make_train_state(cfg, opt, jax.random.key(1),
                             lora_cfg=lora_cfg, params=params)
    dims = ws.dims_from_config(config)
    lora = {}
    for where, i, first, count, stride, _ in block_layout(cfg):
        lora.setdefault(where, []).append({
            t: {"a": jnp.stack([ws.lora_a(dims, key, t, first + r * stride,
                                          4) for r in range(count)]),
                "b": jnp.zeros((count,) + ws.lora_b_shape(dims, t, 4))}
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
        # 128 positions of which 114 are tokens, 3 picks, 4 of 8 held,
        # four layers: about 680 pairs
        assert 450 < pairs < 900


def test_every_adapter_follows_the_reference_after_the_steps(trained):
    """AdamW's steps from the same gradients: every adapter leaf of
    both layer kinds (the first step's update reads the gradient's sign
    alone, the later ones its size)."""
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
    # the mixer's two in 3 layers, attention's four in one, the shared
    # expert's three in all 4
    assert seen == 2 * (3 * 2 + 4 + 4 * 3)


def test_first_gradient_tensor_against_tensor(trained):
    """Leaf by leaf as `correct` compares it on the chip: the program's
    first gradient (Adam's mu after one step) against the reference's."""
    cfg, _, _, model, steps = trained
    reference = steps[0][1]["grad_norm"]
    assert set(reference) == {f"{t}.{k}" for k in "ab" for t in
                              ws.MIXER + ws.ATTENTION + ws.SHARED}
    assert all(v > 0 for k, v in reference.items() if k.endswith(".b"))
    assert len(model.first_gradient) == 4
    assert set(model.first_gradient[0]) == set(ws.MIXER + ws.SHARED)
    assert set(model.first_gradient[2]) == set(ws.ATTENTION + ws.SHARED)
    table = drv.gradient_table(model.first_gradient, model.first_gradient)
    assert drv.gradient_readings(table) == {"grad_dir_gap": 0.0,
                                            "ssm_dir_gap": 0.0}
    # a fault in the mixers' leaves alone moves ssm_dir_gap
    off = [{t: {k: v * (1.5 if t == "in_proj" else 1.0)
                for k, v in ab.items()} for t, ab in layer.items()}
           for layer in model.first_gradient]
    got = drv.gradient_readings(drv.gradient_table(
        off, model.first_gradient))
    assert got["ssm_dir_gap"] == pytest.approx(0.5)
    assert drv.direction_gap(drv.gradient_table(
        off, model.first_gradient), ws.ATTENTION + ws.SHARED) == 0.0


# ---------------------------------------------------------------------------
# the whole model's logits; the multipliers
# ---------------------------------------------------------------------------

def reference_logits(model, batch):
    x = model.outer("embed")[jnp.asarray(batch["inputs"])]
    for i in range(model.sizes["layers"]):
        x, _ = ref.layer_fwd(x, model.layer(i), {}, model.hp,
                             jnp.asarray(batch["positions"]),
                             jnp.asarray(batch["segment_ids"]), "f32")
    x = ref.dd.rms_norm(x, model.outer("final_norm"), 1e-5)
    return np.asarray(jnp.matmul(x, model.outer("lm_head"),
                                 precision=ref.HI))


def program_logits(cfg, params, batch):
    return np.asarray(forward(
        params, jnp.asarray(batch["inputs"]), cfg,
        positions=jnp.asarray(batch["positions"]),
        segment_ids=jnp.asarray(batch["segment_ids"])))


@pytest.fixture(scope="module")
def unquantised():
    config = small_config()
    cfg = model_cfg(config)
    params = jax.jit(drv.params_maker(cfg, config, quant_kind=None))(
        wts.seed_key(11))
    model = ref.Model(config, 11, store_dtype="float32", quant_kind=None)
    batch = packed_batch(rows=1, seed=3)
    return config, cfg, params, model, batch


def test_logits_against_the_reference(unquantised):
    _, cfg, params, model, batch = unquantised
    assert "lm_head" not in params and cfg.tie_embeddings
    real = np.asarray(batch["segment_ids"]) != 0
    np.testing.assert_allclose(program_logits(cfg, params, batch)[real],
                               reference_logits(model, batch)[real],
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("left_out", [
    {"embed_multiplier": None}, {"residual_multiplier": 1.0},
    {"logits_scaling": 1.0}, {"attn_scale": None}])
def test_each_multiplier_changes_the_result(unquantised, left_out):
    _, cfg, params, model, batch = unquantised
    real = np.asarray(batch["segment_ids"]) != 0
    want = reference_logits(model, batch)[real]
    sound = np.abs(program_logits(cfg, params, batch)[real] - want).max()
    other = program_logits(dataclasses.replace(cfg, **left_out), params,
                           batch)[real]
    # weights of std 0.02 make near-uniform attention, so its scale
    # moves the logits least: still 50 x what rounding moves them
    assert np.abs(other - want).max() > 50 * sound, left_out


# ---------------------------------------------------------------------------
# the scan and the conv
# ---------------------------------------------------------------------------

def scan_inputs(S=48, H=4, P=8, G=2, N=6, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    seg = np.zeros((2, S), np.int32)
    seg[0, :7], seg[0, 7:30], seg[0, 30:41] = 1, 2, 3   # 7 padded
    seg[1, :20], seg[1, 20:] = 1, 2
    return (jax.random.normal(k[0], (2, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (2, S, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (2, S, G, N)),
            jax.random.normal(k[4], (2, S, G, N)),
            jax.random.normal(k[5], (H,))), jnp.asarray(seg), \
        jax.random.normal(k[6], (2, S, H, P))


@pytest.mark.parametrize("chunk", [8, 16, 48, 5])
def test_chunked_scan_is_the_recurrence(chunk):
    """Chunks of 8 and 16 cut the documents (boundaries at 7, 30, 41 and
    20) anywhere; 48 is one chunk a row; 5 does not divide the row and
    runs at 4. Forward and every gradient, with highest precision."""
    args, seg, cot = scan_inputs()
    with jax.default_matmul_precision("highest"):
        def chunked(*a):
            return ssm.ssd_scan(*a, seg, chunk=chunk, head_block=1)

        def recurrence(*a):
            return ref.selective_scan(*a, seg)
        got, vjp = jax.vjp(chunked, *args)
        want, ref_vjp = jax.vjp(recurrence, *args)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        for g, w, name in zip(vjp(cot), ref_vjp(cot),
                              ("x", "dt", "a", "B", "C", "D")):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                       err_msg=name)
    assert ssm.scan_geometry(48, chunk) == {8: (8, 6), 16: (16, 3),
                                            48: (48, 1), 5: (4, 12)}[chunk]


def kernel_inputs(bounds, H=16, G=1, S=512, P=64, N=128, seed=0):
    """A row of 512 positions in chunks of 128 at sizes the kernels
    take (heads of 64, a state of 128): documents cut at ``bounds``."""
    k = jax.random.split(jax.random.key(seed), 7)
    seg = np.ones((1, S), np.int32)
    for at in bounds:
        seg[0, at:] += 1
    return (jax.random.normal(k[0], (1, S, H, P)),
            0.3 * jax.nn.softplus(jax.random.normal(k[1], (1, S, H))),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            0.3 * jax.random.normal(k[3], (1, S, G, N)),
            0.3 * jax.random.normal(k[4], (1, S, G, N)),
            jax.random.normal(k[5], (H,))), jnp.asarray(seg), \
        jax.random.normal(k[6], (1, S, H, P))


@pytest.mark.parametrize("bounds,groups,heads_a_step", [
    ((), 1, 16),                    # one document
    ((256,), 1, 16),                # a boundary at a chunk's first position
    ((200,), 2, 8),                 # mid-chunk; a group a grid step
    ((140, 230), 1, 8),             # two in one chunk; two steps a group
    ((50, 450), 1, 16),             # a document longer than several chunks
    ((128, 200, 210, 384), 2, 8),   # all of them
])
def test_the_kernel_pair_is_the_scan(bounds, groups, heads_a_step):
    """``ssd_fwd`` and ``ssd_states`` + ``ssd_bwd`` (interpreted here)
    against the ``jax.numpy`` form and against the recurrence position
    by position: values and all six gradients, float32."""
    args, seg, cot = kernel_inputs(bounds, G=groups)
    plan = ssm.scan_plan(512, 128, 16, 64, 128, groups, heads_a_step)
    assert plan == ssm.ScanPlan("pallas", 128, 4, heads_a_step)
    assert plan.grid_steps(16) == 4 * 16 // heads_a_step
    with jax.default_matmul_precision("highest"):
        def kernels(*a):
            return ssm.ssd_scan(*a, seg, chunk=128,
                                head_block=heads_a_step)

        def numpy_form(*a):
            return ssm._scan_xla(*a, seg, ssm.ScanPlan("xla", 128, 4, 4))

        def recurrence(*a):
            return ref.selective_scan(*a, seg)
        got, vjp = jax.vjp(kernels, *args)
        grads = vjp(cot)
        for other in (numpy_form, recurrence):
            want, other_vjp = jax.vjp(other, *args)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                       err_msg=other.__name__)
            for g, w, name in zip(grads, other_vjp(cot),
                                  ("x", "dt", "a", "B", "C", "D")):
                np.testing.assert_allclose(
                    g, w, rtol=2e-4, atol=2e-5 * float(jnp.abs(w).max()),
                    err_msg=f"{other.__name__}: {name}")


@pytest.mark.parametrize("seq,chunk,sizes,want", [
    (48, 5, (4, 8, 6, 2), ("xla", 4, 12, 2)),
    (48, 48, (4, 8, 6, 2), ("xla", 48, 1, 2)),
    (512, 128, (16, 64, 128, 2), ("pallas", 128, 4, 8)),
    (8192, 256, (128, 64, 128, 1), ("pallas", 256, 32, 16)),
    # a row that is no multiple of the chunk, a state or a head that
    # fills no lane tile, a group too small for a sublane tile
    (8000, 256, (128, 64, 128, 1), ("xla", 250, 32, 16)),
    (8192, 256, (128, 64, 16, 1), ("xla", 256, 32, 16)),
    (8192, 256, (128, 48, 128, 1), ("xla", 256, 32, 16)),
    (8192, 256, (128, 64, 128, 32), ("xla", 256, 32, 4)),
])
def test_the_shape_rule_picks_the_form(seq, chunk, sizes, want):
    """One function reads the sizes (heads, head size, state, groups):
    the kernels where they tile, else the ``jax.numpy`` form."""
    assert ssm.scan_plan(seq, chunk, *sizes) == ssm.ScanPlan(*want)



def test_state_and_taps_start_again_at_every_document():
    """The mechanism's two faults, planted: a state carried across a
    boundary, and a conv that reads the previous document's last three
    positions, each give another result."""
    args, seg, _ = scan_inputs()
    sound = ssm.ssd_scan(*args, seg, chunk=8)
    carried = ssm.ssd_scan(*args, None, chunk=8)
    assert np.abs(np.asarray(sound - carried))[0, 7:30].max() > 1e-2
    # before the first boundary nothing differs
    np.testing.assert_allclose(sound[0, :7], carried[0, :7], rtol=1e-5,
                               atol=1e-6)
    x = jax.random.normal(jax.random.key(1), (2, 48, 10))
    w = jax.random.normal(jax.random.key(2), (10, 4))
    b = jax.random.normal(jax.random.key(3), (10,))
    conv = ssm.causal_conv(x, w, b, seg)
    np.testing.assert_allclose(conv, ref.causal_conv(x, w, b, seg),
                               rtol=1e-5, atol=1e-6)
    leaky = np.asarray(ssm.causal_conv(x, w, b, None))
    # a document's first three positions read across the boundary
    assert np.abs(leaky[0, 7:10] - np.asarray(conv)[0, 7:10]).max() > 1e-2
    np.testing.assert_allclose(leaky[0, 10:30], conv[0, 10:30], rtol=1e-5,
                               atol=1e-6)


def test_a_packed_row_equals_its_documents_run_alone(unquantised):
    """State and conv both: the logits of each document inside a packed
    row are the logits of that document in a row of its own."""
    _, cfg, params, _, batch = unquantised
    packed = program_logits(cfg, params, batch)[0]
    seg = np.asarray(batch["segment_ids"])[0]
    for doc in (1, 2, 3, 4):
        at = np.flatnonzero(seg == doc)
        alone = {k: np.zeros_like(v) for k, v in batch.items()}
        n = len(at)
        for k in ("inputs", "positions"):
            alone[k][0, :n] = batch[k][0, at]
        alone["segment_ids"][0, :n] = 1
        np.testing.assert_allclose(
            program_logits(cfg, params, alone)[0, :n], packed[at],
            rtol=2e-4, atol=2e-5, err_msg=f"document {doc}")


# ---------------------------------------------------------------------------
# one rank's share of the routed layer
# ---------------------------------------------------------------------------

def routed_layer(seed=3, D=32, E=8, F=16, Fs=24):
    k = jax.random.split(jax.random.key(seed), 8)
    W = {"router": jax.random.normal(k[0], (D, E)) * 0.7,
         "expert_gate": jax.random.normal(k[1], (E, D, F)) * 0.2,
         "expert_up": jax.random.normal(k[2], (E, D, F)) * 0.2,
         "expert_down": jax.random.normal(k[3], (E, F, D)) * 0.2,
         "shared_gate": jax.random.normal(k[4], (D, Fs)) * 0.2,
         "shared_up": jax.random.normal(k[5], (D, Fs)) * 0.2,
         "shared_down": jax.random.normal(k[6], (Fs, D)) * 0.2}
    return W, jax.random.normal(k[7], (2, 24, D))


def reference_share(x, W, held):
    lo, hi = held
    hp = {"top_k": 3, "held": hi - lo, "held_lo": lo}
    Wh = dict(W, **{n: W[n][lo:hi] for n in ws.EXPERT})
    return ref.routed(x, Wh, hp, jnp.ones(x.shape[:-1], bool), "f32")


def test_four_shares_add_up_to_the_uncut_layer():
    """What the four ranks of a 4-way expert-parallel layer compute (each
    its two experts' pairs, weighted by the softmax over all three
    logits a token selected), with the shared expert, which every rank
    computes alike, counted once, is the uncut reference's layer."""
    W, x = routed_layer()
    whole, pairs = reference_share(x, W, (0, 8))
    assert int(pairs) == 2 * 24 * 3
    shared = ref.em.swiglu(x, W["shared_gate"], W["shared_up"],
                           W["shared_down"], None, None, None, 0.0, "f32")
    total = jnp.zeros_like(whole)
    for lo in range(0, 8, 2):
        cfg = tiny(d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64,
                   n_experts=8, expert_top_k=3, expert_d_ff=16,
                   n_shared_experts=1, shared_d_ff=24,
                   router="topk_softmax", experts_held=(lo, lo + 2))
        lp = {"router": W["router"],
              "w_gate": W["expert_gate"][lo:lo + 2],
              "w_up": W["expert_up"][lo:lo + 2],
              "w_down": W["expert_down"][lo:lo + 2],
              **{n: W[n] for n in ws.SHARED}}
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
    # a token's weights over all it selected add up to one
    sel, w = ref.route(x, W["router"], {"top_k": 3})
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, rtol=1e-5)
    assert int(jnp.sum(sel)) == 2 * 24 * 3


# ---------------------------------------------------------------------------
# the preset, the configuration file, the counts
# ---------------------------------------------------------------------------

def test_the_preset_is_the_published_configuration():
    cfg = granite_4_0_h_small()
    assert preset_for_model_id("ibm-granite/granite-4.0-h-small") == cfg
    assert PRESETS["granite-4.0-h-small"] is granite_4_0_h_small
    assert cfg.block_pattern.count("ssm") == 9 and cfg.n_repeats == 4
    assert [i for i in range(40) if cfg.block_kind(i) == "global"] \
        == [5, 15, 25, 35]
    assert cfg.ssm_leaf_shapes()["in_proj"] == (4096, 16768)
    # 32B-A9B
    assert cfg.param_count() == pytest.approx(32.2e9, rel=5e-3)
    assert cfg.active_param_count() == pytest.approx(8.8e9, rel=5e-3)
    # what the driver builds from the configuration file's published
    # values is the preset
    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    cell = "granite4hsmall_ep4_l20.qlora_sft_packed_8k_ssm"
    c = hs.cell_files(bench, hs.find_cell(bench, cell))["config"]
    published = dict(c, **c["published"])
    published.pop("experts_held"), published.pop("router_outputs")
    catalog_layers = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert c["layer_types"] == catalog_layers[:20]
    published["layer_types"] = catalog_layers
    built = drv.model_config(published, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype,
                             max_seq_len=cfg.max_seq_len)
    assert built.experts_held == (0, 72)
    assert dataclasses.replace(built, name=cfg.name,
                               experts_held=None) == cfg
    # the cell's own: two periods, 18 held of 72, a quarter of the ids
    cut = drv.model_config(c, dtype="bfloat16", param_dtype="bfloat16",
                           max_seq_len=8192)
    assert (cut.n_layers, cut.n_repeats, cut.held_range, cut.n_experts,
            cut.vocab_size) == (20, 2, (0, 18), 72, 25088)
    assert cut.n_ssm_layers == 18
    # a model without the new fields keeps the digest it was recorded
    # under
    assert not {"ssm_heads", "residual_multiplier", "shared_d_ff"} \
        & set(tiny().to_dict())
    assert ModelConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError, match="ssm_heads"):
        tiny(block_pattern=("ssm", "global"))


def test_the_configuration_file_keeps_the_published_widths():
    import json
    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite4hsmall_ep4_l20")
    c = hs.load_json(hs.ROOT, entry["file"])
    assert entry["source"] == c["source"] and "granite-4.0-h-small" \
        in entry["source"]
    assert sorted(c["reduced"]) == sorted(entry["reduced"]) == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    widths = {
        "hidden_size": 4096, "intermediate_size": 768,
        "shared_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts_per_tok": 10,
        "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_chunk_size": 256, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "logits_scaling": 16, "router_outputs": 72}
    for key, value in widths.items():
        assert c[key] == value, key
    assert c["published"] == {"num_hidden_layers": 40,
                              "num_local_experts": 72,
                              "vocab_size": 100352}
    assert set(c["cut"]) == set(c["reduced"])
    assert set(c["assumed"]) >= {"expert_width", "in_proj_columns",
                                 "gate_and_norm", "time_step_limit",
                                 "weights"}
    assert "2 pipeline stages of 20 layers" in c["deployment"]
    # floors of a cut: two whole periods, 8 experts, an eighth of the ids
    assert c["num_local_experts"] >= 8 and c["vocab_size"] * 8 >= 100352
    mix = hs.load_json(hs.BENCH_DIR, "traffic",
                       "qlora_sft_packed_8k_ssm.json")
    routed = hs.load_json(hs.BENCH_DIR, "traffic",
                          "qlora_sft_packed_8k.json")
    assert mix["kind"] == "train_ssm" and mix["rows"] == routed["rows"]
    assert {**routed["job"], "MODEL_ID": "ibm-granite/granite-4.0-h-small"} \
        == mix["job"]
    assert len(json.dumps(bench)) < 64 * 1024


# ---------------------------------------------------------------------------
# trees, adapters, quantised init, checkpoints' names, scopes, refusals
# ---------------------------------------------------------------------------

def hybrid_tiny(**kw):
    return tiny(**{**dict(
        vocab_size=128, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=64, block_pattern=("ssm", "global"), rope_kinds=(),
        ssm_heads=4, ssm_head_dim=32, ssm_state=8, ssm_chunk=8,
        n_experts=8, expert_top_k=3, expert_d_ff=64, n_shared_experts=1,
        shared_d_ff=128, router="topk_softmax", tie_embeddings=True,
        embed_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0), **kw})


@pytest.mark.parametrize("preset,kind,leaves", [
    ("mistral-7b", "dense",
     ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
      "w_down"]),
    ("k-exaone-236b", "moe",
     ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router", "w_gate",
      "w_up", "w_down", "router_bias", "shared_gate", "shared_up",
      "shared_down", "q_norm", "k_norm"]),
    ("glm-4.7-flash", "moe",
     ["attn_norm", "wq_a", "q_latent_norm", "wq_b", "wkv_a",
      "kv_latent_norm", "wkv_b", "wo", "mlp_norm", "router", "w_gate",
      "w_up", "w_down", "router_bias", "shared_gate", "shared_up",
      "shared_down"]),
])
def test_the_other_families_keep_their_trees(preset, kind, leaves):
    """Creation order too: the init keys are drawn in it. The shared
    expert's width is still the routed experts' times their count."""
    cfg = PRESETS[preset]()
    assert list(block_leaves(cfg, 1, kind)) == leaves
    if cfg.n_shared_experts:
        assert block_leaves(cfg, 1, kind)["shared_gate"][0][-1] \
            == cfg.n_shared_experts * cfg.expert_d_ff


def test_a_state_space_layer_has_the_mixers_leaves():
    cfg = granite_4_0_h_small()
    moe = ["mlp_norm", "router", "w_gate", "w_up", "w_down", "shared_gate",
           "shared_up", "shared_down"]
    assert list(block_leaves(cfg, 1, "moe", "ssm")) \
        == ["attn_norm"] + MIXER_LEAVES + moe
    assert list(block_leaves(cfg, 1, "moe", "global")) \
        == ["attn_norm", "wq", "wk", "wv", "wo"] + moe
    assert block_leaves(cfg, 1, "moe")["shared_gate"][0] == (1, 4096, 1536)
    params = init_params(hybrid_tiny(), jax.random.key(0))
    mixer = params["blocks"][0]
    # decays that are real ones: A in [-16, -1], steps in [0.001, 0.1]
    a = -np.exp(np.asarray(mixer["a_log"]))
    assert a.min() >= -16 and a.max() <= -1
    dt = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert np.all(np.asarray(mixer["d_skip"]) == 1)
    assert np.abs(np.asarray(mixer["conv_w"])).max() <= 0.5
    assert "wq" in params["blocks"][1] and "in_proj" not in params["blocks"][1]


def test_lora_and_quantised_init_take_the_mixers_projections():
    from gke_ray_train_tpu.models.qinit import init_quantized_params
    from gke_ray_train_tpu.ops.quant import is_qtensor
    from gke_ray_train_tpu.train.lora import (
        LoraConfig, _effective_targets, init_lora, lora_specs, merge_lora)
    cfg = hybrid_tiny()
    default = LoraConfig(r=4)
    assert _effective_targets(cfg, default, "moe", "ssm") \
        == ("in_proj", "out_proj") + SHARED_MLP[0]
    assert _effective_targets(cfg, default, "moe", "global") \
        == ("wq", "wk", "wv", "wo") + SHARED_MLP[0]
    # a job that names q and v alone adapts the first projection
    assert _effective_targets(
        cfg, LoraConfig(r=4, targets=("wq", "wv")), "moe", "ssm") \
        == ("in_proj",)
    # the other families are told what they were told
    assert _effective_targets(tiny(), default) == default.targets
    lora = init_lora(cfg, default, jax.random.key(0))
    specs = lora_specs(cfg, default)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, lora)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: not isinstance(
                s, (dict, list))))
    assert lora["blocks"][0]["in_proj"]["b"].shape == (2, 4, 128 + 144 + 4)
    assert set(lora["blocks"][1]) == {"wq", "wk", "wv", "wo"} \
        | set(SHARED_MLP[0])
    params = init_quantized_params(cfg, jax.random.key(0))
    mixer = params["blocks"][0]
    for name in ("in_proj", "out_proj", "shared_gate", "w_gate"):
        assert is_qtensor(mixer[name]), name
    for name in ("conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
                 "ssm_norm", "router"):
        assert not is_qtensor(mixer[name]), name
    assert mixer["in_proj"].codes.dtype == jnp.uint4
    lora = jax.tree.map(lambda x: x + 0.01, lora)
    toks = jnp.arange(32).reshape(2, 16) % 128
    want = forward(params, toks, cfg, lora=lora, lora_scale=default.scale)
    got = forward(merge_lora(params, lora, default, on_host=True), toks, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def granite_share(**kw):
    return granite_4_0_h_small(**{**dict(
        n_layers=20, vocab_size=25088, experts_held=(0, 18),
        max_seq_len=8192, dtype="bfloat16", param_dtype="bfloat16"), **kw})


def test_chooser_names_and_bytes_of_a_state_space_layer():
    """One row of 8192, bf16: 18 mixers, 2 attention layers, 20 routed
    layers with a shared expert of 1536."""
    got = dict(remat.keep_candidates(granite_share(), 1, 8192))
    T = 8192
    assert got == {
        "attn/core": T * 2 * 32 * (128 * 2 + 4),
        "attn/qkv": T * 2 * (32 + 16) * 128 * 2,
        "attn/out": T * 2 * 4096 * 2,
        "ssm/scan": T * 18 * 8192 * 2,
        "ssm/out": T * 18 * 4096 * 2,
        "moe/shared": T * 20 * 2 * 1536 * 2,
        "moe/experts": T * 20 * 2 * 768 * 2 * 10,
        "ssm/in_proj": T * 18 * 16768 * 2}
    assert [n for n in remat.KEEP_ORDER if n in got] == list(got)
    # no other family has the names, and theirs cost what they did
    from gke_ray_train_tpu.models.config import k_exaone_236b, mistral_7b
    for other in (mistral_7b(), k_exaone_236b()):
        names = dict(remat.keep_candidates(other, 1, 1024))
        assert not [n for n in names if n.startswith("ssm/")]
    assert dict(remat.keep_candidates(mistral_7b(), 1, 1024))[
        "attn/out"] == 1024 * 32 * 4096 * 2
    # the cell's rows take the kernels, the tiny model's the jax.numpy form
    assert ssm_geometry(granite_share(), 8192) == {
        "impl": "pallas", "chunk": 256, "chunks_a_row": 32, "heads": 128,
        "head_dim": 64, "state": 128, "groups": 1, "head_block": 16,
        "grid_steps_a_row": 256, "layers": 18}
    assert ssm_geometry(hybrid_tiny(), 64) == {
        "impl": "xla", "chunk": 8, "chunks_a_row": 8, "heads": 4,
        "head_dim": 32, "state": 8, "groups": 1, "head_block": 4,
        "grid_steps_a_row": 0, "layers": 2}
    assert ssm_geometry(tiny(), 128) == {}


@pytest.mark.parametrize("form,seq,lengths,sizes", [
    ("xla", 64, (21, 5, 14, 17), {}),
    ("pallas", 256, (90, 30, 60, 70), dict(
        ssm_heads=8, ssm_head_dim=64, ssm_state=128, ssm_chunk=128)),
])
def test_scopes_and_kept_names_of_the_mixer(devices, form, seq, lengths,
                                            sizes):
    """The mixer's stages are in the compiled step's scope table; the
    scan's output kept spares conv and scan their second run, and the
    first projection runs again (its adapters' gradients read its
    input). At 64 positions the scan is the ``jax.numpy`` form; at 256
    in chunks of 128 the kernels (interpreted here: their operations
    carry the kernel's name): all of them under ``ssm/scan``, and with
    ``ssm/scan`` kept the rematerialised part holds no operation of the
    forward kernel."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    from gke_ray_train_tpu.ops.quant import quantize_params
    from gke_ray_train_tpu.train import (
        LoraConfig, make_optimizer, make_train_state, make_train_step)
    assert {"ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm",
            "ssm/out_proj"} <= set(obs_trace.SCOPE_NAMES)
    assert obs_trace.SCOPE_VERSION == 4
    assert "ssm_scan" in obs_trace.SPAN_NAMES["step_build"]
    assert obs_trace.check_schema() == []
    cfg = hybrid_tiny(n_layers=2, remat=True, attn_impl="xla",
                      max_seq_len=seq, **sizes)
    assert ssm_geometry(cfg, seq)["impl"] == form
    opt = make_optimizer(1e-2)
    lora_cfg = LoraConfig(r=4, alpha=8)
    params = quantize_params(init_params(cfg, jax.random.key(0)), "nf4")
    state = make_train_state(cfg, opt, jax.random.key(1),
                             lora_cfg=lora_cfg, params=params)
    batch = {k: jnp.asarray(v) for k, v in packed_batch(
        seq=seq, vocab=128, lengths=lengths).items()}

    def paths(keep):
        step = make_train_step(cfg, opt, lora_cfg=lora_cfg, grad_accum=2,
                               donate=False, remat_keep=keep)
        table = obs_trace.scope_table(
            step.lower(state, batch).compile().as_text())
        every = {obs_trace.scope_path(op) for op in table.values()}
        again = {obs_trace.scope_path(op) for op in table.values()
                 if "rematted_computation" in op}
        kernels = {(name, obs_trace.scope_path(op),
                    "rematted_computation" in op)
                   for op in table.values()
                   for name in ("ssd_fwd", "ssd_states", "ssd_bwd")
                   if f"/{name}/" in op}
        return every, again, kernels

    every, again, kernels = paths(())
    assert {"ssm/in_proj/base", "ssm/in_proj/lora", "ssm/conv", "ssm/scan",
            "ssm/gate_norm", "ssm/out_proj/base", "attn/core/full",
            "moe/experts", "moe/shared/base"} <= every
    assert {"ssm/scan", "ssm/conv", "ssm/in_proj/base"} <= again
    _, kept, kernels_kept = paths(("ssm/scan",))
    assert "ssm/in_proj/base" in kept
    assert len([p for p in kept if p == "ssm/scan"]) \
        <= len([p for p in again if p == "ssm/scan"])
    if form == "pallas":
        # the forward kernel runs again only where its output is not kept
        assert kernels == {
            ("ssd_fwd", "ssm/scan", False), ("ssd_fwd", "ssm/scan", True),
            ("ssd_states", "ssm/scan", False), ("ssd_bwd", "ssm/scan", False)}
        assert kernels_kept == kernels - {("ssd_fwd", "ssm/scan", True)}
    else:
        assert not kernels and not kernels_kept


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e: libtpu compiles for it with no
    chip attached (and looks nothing up: both variables are set), or
    the tests that ask for it are skipped."""
    with pytest.MonkeyPatch.context() as mp:
        for name, value in (("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                            ("TPU_WORKER_HOSTNAMES", "localhost")):
            if name not in os.environ:
                mp.setenv(name, value)
        try:
            from jax.experimental import topologies
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:1x1",
                chips_per_host_bounds=(1, 1, 1)).devices[0]
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no TPU compiler: {type(e).__name__}: {e}")


def test_v5e_scan_keeps_a_heads_decay_out_of_hbm(v5e):
    """What PR 33's gain rests on, at the cell's geometry (a row of
    8192, 128 heads of 64, state 128, chunks of 256, bf16): compiled
    for the v5e, a mixer's scan forward + backward is the three kernels
    and no float32 ``[.., Q, Q]`` tensor is among the program's own
    operations, where the ``jax.numpy`` form wrote ``f32[1, 32, 1, 16,
    256, 256]`` a block of heads, three times over."""
    from jax.sharding import SingleDeviceSharding
    from gke_ray_train_tpu.plan import XLA_TPU_OPTIONS
    cfg = granite_share()
    S, H, P, N, G, Q = (8192, cfg.ssm_heads, cfg.ssm_head_dim,
                        cfg.ssm_state, cfg.ssm_groups, cfg.ssm_chunk)
    sharding = SingleDeviceSharding(v5e)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def scan(x, dt, a, b, c, d, seg, g, interpret=False):
        def fwd(x, dt, a, b, c, d):
            return ssm.ssd_scan(x.reshape(1, S, H, P), dt, a, b, c, d, seg,
                                chunk=Q, interpret=interpret
                                ).reshape(1, S, H * P)
        y, vjp = jax.vjp(jax.checkpoint(fwd), x, dt, a, b, c, d)
        return y, vjp(g)
    # the suite asks for float32 products (conftest.py), which Mosaic
    # refuses for bf16 operands: the program's own default here
    with jax.default_matmul_precision("default"):
        built = jax.jit(scan, compiler_options=XLA_TPU_OPTIONS).lower(
            spec((1, S, H * P)), spec((1, S, H), jnp.float32),
            spec((H,), jnp.float32), spec((1, S, G, N)), spec((1, S, G, N)),
            spec((H,), jnp.float32), spec((1, S), jnp.int32),
            spec((1, S, H * P))).compile()
    hlo = built.as_text()
    calls = re.findall(r"%[\w.\-]*?(ssd_(?:fwd|states|bwd))[\w.\-]* = .*"
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    assert sorted(calls) == ["ssd_bwd", "ssd_fwd", "ssd_states"], calls
    assert not re.findall(rf"f32\[[\d,]*{Q},{Q}\]", hlo)
    # x, y and the two cotangents are 134 MB each; a block's decay was 134
    assert built.memory_analysis().temp_size_in_bytes < 2 * S * H * P * 2


def test_v5e_no_pick_row_reaches_hbm(v5e):
    """What the gain rests on, at the hybrid cell's shape (8192 tokens,
    10 picks, rows of 4096 in bf16): compiled for the v5e, the combine's
    forward and the dispatch's backward are one ``moe_gather_sum`` call
    each, and no ``[T, K, D]`` tensor, bf16 or float32, is among the
    program's operations (before PR 35 XLA wrote ``f32[8192, 10, 4096]``
    and the gathered rows in bf16)."""
    from jax.sharding import SingleDeviceSharding
    T, K, D = 8192, 10, 4096
    P = T * K
    sharding = SingleDeviceSharding(v5e)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def layer(x, tok, row, held, w, order, dy):
        def f(x):
            xs = moe._dispatch(x, tok, row, held)
            return moe._combine(xs * 2, w, tok, row, order)
        y, vjp = jax.vjp(f, x)
        return y, vjp(dy)
    # the suite asks for float32 products (conftest.py); the program's
    # own default here
    with jax.default_matmul_precision("default"), \
            pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "interpret_default", lambda interpret: False)
        built = jax.jit(layer).lower(
            spec((T, D)), spec((P,), jnp.int32), spec((T, K), jnp.int32),
            spec((T, K), jnp.bool_), spec((T, K), jnp.float32),
            spec((P,), jnp.int32), spec((T, D))).compile()
    hlo = built.as_text()
    calls = re.findall(r"%[\w.\-]*?(moe_gather_sum)[\w.\-]* = .*"
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(calls) == 2, calls
    assert not re.findall(rf"\[{T},{K},{D}\]", hlo)


def test_serving_a_pipelined_mesh_and_the_converters_refuse_by_name(devices):
    from gke_ray_train_tpu.ckpt import hf_io
    from gke_ray_train_tpu.models.kvcache import require_decodable
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    with pytest.raises(NotImplementedError,
                       match="granite-4.0-h-small.*a recurrent state "
                             "beside keys and values"):
        require_decodable(granite_4_0_h_small())
    cfg = hybrid_tiny(n_layers=2)
    mesh = build_mesh(MeshConfig(pipe=2, data=1, fsdp=4), devices)
    with pytest.raises(NotImplementedError, match="no state-space layer"):
        forward(init_params(cfg, jax.random.key(0)),
                jnp.zeros((4, 16), jnp.int32), cfg, mesh=mesh)
    with pytest.raises(NotImplementedError, match="no name map"):
        hf_io.require_name_map(granite_4_0_h_small())
    hf_io.require_name_map(tiny())


@pytest.mark.parametrize("layers,seq,xla_gb", [
    (10, 8192, 5.093), (20, 8192, 7.344), (30, 8192, 8.276),
    (10, 4096, 2.953), (20, 4096, 4.423)])
def test_working_set_estimate_errs_high_by_little(layers, seq, xla_gb):
    """``compiled.memory_analysis().peak_memory_in_bytes`` less the
    arguments, with nothing kept, of the share's QLoRA step (r = 64, one
    row a micro-pass) compiled for a described v5e chip (PERF.md, PR 32):
    the estimate reads 0.08-0.20 GB above it, one period (no loop over
    periods) and several."""
    from gke_ray_train_tpu.train.lora import LoraConfig, init_lora
    cfg = granite_share(n_layers=layers, max_seq_len=seq)
    lora = jax.eval_shape(
        lambda k: init_lora(cfg, LoraConfig(r=64), k), jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(lora))
    got = remat.working_set_bytes(
        cfg, 1, seq, model=1, trainable_bytes=4 * n,
        trainable_full_bytes=4 * n, cast_bytes=2 * n) / 1e9
    assert 0.05 < got - xla_gb < 0.25, got
