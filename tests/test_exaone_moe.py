"""The sigmoid-routed decoder (K-EXAONE's layer: ``router="sigmoid"``, a
held range of experts, a shared expert, a leading dense layer, q/k norm,
window layers with rotary and full layers without) against the plain
reference ``benchmark/reference/exaone_moe_decoder.py`` on seeded random
weights at a small size, and the reference against ``transformers``' own
modules of the two families it follows.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights as wts
from benchmark import weights_moe as wm
from benchmark.drivers import train_moe as drv
from benchmark.reference import exaone_moe_decoder as ref
from gke_ray_train_tpu.data.packing import pack_examples
from gke_ray_train_tpu.models.config import ModelConfig, k_exaone_236b, tiny
from gke_ray_train_tpu.models.transformer import block_layout
from gke_ray_train_tpu.ops import moe

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_TYPES = ["sliding_attention"] * 3 + ["full_attention"]


def small_config(**over):
    """The published keys at a small size: a dense layer and seven
    sparse ones (two periods of LLLG), 16 router outputs of which
    experts 4-7 are held, 4 a token, window 8."""
    config = {
        "model_type": "exaone_moe", "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 128, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 8, "num_hidden_layers_published": 8,
        "layer_types": LAYER_TYPES * 2,
        "mlp_layer_types": ["dense"] + ["sparse"] * 7,
        "first_k_dense_replace": 1, "vocab_size": 96,
        "max_position_embeddings": 64, "sliding_window": 8,
        "moe_intermediate_size": 64, "num_experts": 4,
        "experts_held": [4, 8], "router_outputs": 16,
        "num_experts_per_tok": 4, "num_shared_experts": 1,
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "rms_norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
        "tie_word_embeddings": False}
    config.update(over)
    return config


def packed_batch(rows=2, seq=64, seed=0, vocab=96):
    """Rows packed from several documents, so that windows and document
    boundaries cross."""
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


@pytest.fixture(scope="module")
def trained():
    """Three optimizer steps of the program (the preset's fields through
    the benchmark's seam, ``make_train_state``, ``make_train_step``,
    the job's own optimizer) and of the reference, from the same seed."""
    from benchmark.drivers.train import optimizer_facts
    from gke_ray_train_tpu.config import (
        optimizer_from_config, schedule_from_config)
    from gke_ray_train_tpu.train import (
        LoraConfig, make_train_state, make_train_step)
    config = small_config()
    cfg = drv.model_config(config, dtype="float32", param_dtype="float32",
                           attn_impl="xla", max_seq_len=64)
    assert (cfg.prologue_layers, cfg.n_repeats) == (4, 1)
    lora_cfg = LoraConfig(r=4, alpha=8)
    opt = optimizer_from_config(JOB, schedule_from_config(JOB, 10))
    key = wts.seed_key(7)
    params = jax.jit(drv.params_maker(cfg, config, quant_kind="nf4"))(key)
    state = make_train_state(cfg, opt, jax.random.key(1),
                             lora_cfg=lora_cfg, params=params)
    dims = wm.dims_from_config(config)
    lora = {}
    for where, i, first, count, stride, _ in block_layout(cfg):
        lora.setdefault(where, []).append({
            t: {"a": jnp.stack([wm.lora_a(dims, key, t, first + r * stride,
                                          4) for r in range(count)]),
                "b": jnp.zeros((count,) + wm.lora_b_shape(dims, t, 4))}
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
        # 128 positions of which 114 are tokens, 4 picks, 4 of 16 held,
        # seven sparse layers: about 800 pairs
        assert 500 < pairs < 1100


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
    # attention in 8 layers, the dense MLP in one, the shared expert in 7
    assert seen == 2 * (8 * 4 + 3 + 7 * 3)


def test_first_gradient_follows_the_reference(trained):
    """Leaf by leaf over all layers, as `correct` compares it."""
    _, _, _, _, steps = trained
    reference = steps[0][1]["grad_norm"]
    assert set(reference) == {f"{t}.{k}" for k in "ab" for t in
                              wm.ATTENTION + wm.DENSE_MLP + wm.SHARED}
    assert all(v > 0 for k, v in reference.items() if k.endswith(".b"))


# ---------------------------------------------------------------------------
# the routed layer by itself
# ---------------------------------------------------------------------------

def layer_weights(seed=3, D=32, E=16, F=16, bias=0.1):
    k = jax.random.split(jax.random.key(seed), 6)
    return {"router": jax.random.normal(k[0], (D, E)) * 0.7,
            "router_bias": jax.random.normal(k[1], (E,)) * bias,
            "expert_gate": jax.random.normal(k[2], (E, D, F)) * 0.2,
            "expert_up": jax.random.normal(k[3], (E, D, F)) * 0.2,
            "expert_down": jax.random.normal(k[4], (E, F, D)) * 0.2}


def routed_cfg(held, **kw):
    base = dict(d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64,
                n_experts=16, expert_top_k=4, expert_d_ff=16,
                router="sigmoid", router_bias=True, router_scale=2.5,
                experts_held=held)
    base.update(kw)
    return tiny(**base)


def program_share(x, W, held, **kw):
    lo, hi = held
    lp = {"router": W["router"], "router_bias": W["router_bias"],
          "w_gate": W["expert_gate"][lo:hi], "w_up": W["expert_up"][lo:hi],
          "w_down": W["expert_down"][lo:hi]}
    return moe.routed_experts(x, lp, routed_cfg(held), jnp.float32, **kw)


def reference_share(x, W, held, capacity=None):
    lo, hi = held
    hp = {"top_k": 4, "held": hi - lo, "held_lo": lo, "routed_scale": 2.5}
    Wh = dict(W, **{n: W[n][lo:hi] for n in wm.EXPERT})
    return ref.routed(x, Wh, hp, jnp.ones(x.shape[:-1], bool), "f32",
                      capacity)


def test_shares_add_up_to_the_uncut_layer():
    """What the four ranks of a 4-way expert-parallel layer compute,
    added, is the whole layer's routed part (the shared expert, which
    every rank computes alike, counts once and is no part of it): in
    the program and in the reference, and each share agrees."""
    W = layer_weights()
    x = jax.random.normal(jax.random.key(5), (2, 24, 32))
    whole, pairs = reference_share(x, W, (0, 16))
    assert int(pairs) == 2 * 24 * 4
    total = jnp.zeros_like(whole)
    for lo in range(0, 16, 4):
        y, counters = program_share(x, W, (lo, lo + 4))
        mine, n = reference_share(x, W, (lo, lo + 4))
        np.testing.assert_allclose(y, mine, rtol=1e-4, atol=1e-5)
        assert counters["moe_pairs"] == int(n)
        total = total + y
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    y_all, counters = program_share(x, W, (0, 16))
    np.testing.assert_allclose(y_all, whole, rtol=1e-4, atol=1e-5)
    assert counters["moe_pairs"] == 2 * 24 * 4


def test_no_drop_when_the_router_is_forced_onto_one_expert():
    """Every token picks expert 5 (a selection bias no score can beat):
    one held expert takes a pair from every token, the other three what
    the scores give, and nothing is dropped."""
    W = layer_weights()
    W["router_bias"] = W["router_bias"].at[5].set(10.0)
    x = jax.random.normal(jax.random.key(6), (2, 24, 32))
    y, counters = program_share(x, W, (4, 8))
    mine, n = reference_share(x, W, (4, 8))
    np.testing.assert_allclose(y, mine, rtol=1e-4, atol=1e-5)
    assert counters["moe_pairs_dropped"] == 0
    assert counters["moe_pairs"] == int(n) >= 48
    assert counters["moe_max_load"] >= 48 / (int(n) / 4)
    # with a buffer smaller than the worst case the counter counts
    _, cut = program_share(x, W, (4, 8), buffer_rows=40)
    assert cut["moe_pairs"] == 40
    assert cut["moe_pairs_dropped"] == int(n) - 40


def test_selection_is_on_score_plus_bias_and_weights_from_score():
    cfg = routed_cfg((0, 16))
    x = jax.random.normal(jax.random.key(8), (40, 32))
    W = layer_weights(bias=0.5)
    idx, w = moe.select_experts(x, W["router"], W["router_bias"], cfg)
    s = jax.nn.sigmoid(x @ W["router"])
    assert (np.sort(idx, -1) == np.sort(
        jax.lax.top_k(s + W["router_bias"], 4)[1], -1)).all()
    # the bias moved some selections, or the test shows nothing
    assert (np.sort(idx, -1) != np.sort(jax.lax.top_k(s, 4)[1], -1)).any()
    picked = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)


def test_padding_is_not_routed():
    W = layer_weights()
    x = jax.random.normal(jax.random.key(9), (1, 16, 32))
    valid = jnp.arange(16)[None] < 10
    y, counters = program_share(x, W, (0, 16), valid=valid)
    assert counters["moe_pairs"] == 40
    assert not np.asarray(y[0, 10:]).any()


def test_gradients_through_the_sort_match_the_plain_loop():
    W = layer_weights()
    x = jax.random.normal(jax.random.key(10), (2, 12, 32))
    g = jax.grad(lambda x: jnp.sum(jnp.sin(
        program_share(x, W, (4, 12))[0])))(x)
    g_ref = jax.grad(lambda x: jnp.sum(jnp.sin(
        reference_share(x, W, (4, 12))[0])))(x)
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# attention kinds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_attention_kind_against_the_reference(kind):
    """One layer of each kind over packed rows: sliding layers rotate
    and see 8 keys back within the document, full layers do not rotate
    and see the whole document. The other kind's rule gives another
    result, so the agreement is no accident."""
    from gke_ray_train_tpu.models.transformer import forward
    config = small_config(num_hidden_layers=1, layer_types=[kind],
                          mlp_layer_types=["dense"],
                          num_hidden_layers_published=1)
    cfg = drv.model_config(config, dtype="float32", param_dtype="float32",
                           attn_impl="xla", max_seq_len=64)
    assert cfg.block_pattern == (
        "sliding" if kind.startswith("sliding") else "global",)
    params = jax.jit(drv.params_maker(cfg, config, quant_kind=None))(
        wts.seed_key(11))
    batch = packed_batch(rows=1, seed=3)
    logits = forward(params, jnp.asarray(batch["inputs"]), cfg,
                     positions=jnp.asarray(batch["positions"]),
                     segment_ids=jnp.asarray(batch["segment_ids"]))
    model = ref.Model(config, 11, store_dtype="float32", quant_kind=None)

    def reference_logits(flip=False):
        W = model.layer(0)
        if flip:
            W = dict(W, rotary=~W["rotary"], window=jnp.where(
                W["rotary"], ref.NO_WINDOW, 8))
        x = model.outer("embed")[jnp.asarray(batch["inputs"])]
        x, _ = ref.layer_fwd(x, W, {}, model.hp,
                             jnp.asarray(batch["positions"]),
                             jnp.asarray(batch["segment_ids"]), "f32")
        x = ref.dd.rms_norm(x, model.outer("final_norm"), 1e-5)
        return x @ model.outer("lm_head")
    real = np.asarray(batch["segment_ids"][0]) != 0
    np.testing.assert_allclose(np.asarray(logits[0])[real],
                               np.asarray(reference_logits()[0])[real],
                               rtol=2e-4, atol=2e-5)
    assert np.abs(np.asarray(logits[0])[real]
                  - np.asarray(reference_logits(True)[0])[real]).max() > 1e-3


def test_reference_attention_in_blocks_is_the_plain_one(monkeypatch):
    k = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(k[0], (1, 64, 4, 16))
    kv = [jax.random.normal(x, (1, 64, 2, 16)) for x in k[1:]]
    batch = packed_batch(rows=1, seed=4)
    pos, seg = (jnp.asarray(batch[n]) for n in ("positions", "segment_ids"))
    plain = ref.dd.attention(q, *kv, pos, seg, 8)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    np.testing.assert_allclose(ref.attention(q, *kv, pos, seg, 8), plain,
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the reference against transformers' modules (torch)
# ---------------------------------------------------------------------------

def test_reference_routed_block_against_deepseek_v3_moe():
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip(
        "transformers.models.deepseek_v3.modeling_deepseek_v3")
    from transformers.models.deepseek_v3.configuration_deepseek_v3 import (
        DeepseekV3Config)
    hf = DeepseekV3Config(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
        n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2, vocab_size=32)
    block = tf.DeepseekV3MoE(hf).to(torch.float32)
    W = layer_weights(bias=0.3)
    shared = {n: np.asarray(jax.random.normal(
        jax.random.key(20 + i), (32, 16) if i < 2 else (16, 32))) * 0.2
        for i, n in enumerate(wm.SHARED)}
    with torch.no_grad():
        block.gate.weight.copy_(torch.tensor(np.asarray(W["router"]).T))
        block.gate.e_score_correction_bias.copy_(
            torch.tensor(np.asarray(W["router_bias"])))
        for e, expert in enumerate(block.experts):
            for proj, n in zip(("gate_proj", "up_proj", "down_proj"),
                               wm.EXPERT):
                getattr(expert, proj).weight.copy_(
                    torch.tensor(np.asarray(W[n][e]).T))
        for proj, n in zip(("gate_proj", "up_proj", "down_proj"), wm.SHARED):
            getattr(block.shared_experts, proj).weight.copy_(
                torch.tensor(shared[n].T))
    x = np.asarray(jax.random.normal(jax.random.key(13), (2, 12, 32)))
    with torch.no_grad():
        theirs = block(torch.tensor(x)).numpy()
    y, pairs = reference_share(jnp.asarray(x), W, (0, 16))
    y = y + ref.swiglu(jnp.asarray(x), *(jnp.asarray(shared[n])
                                         for n in wm.SHARED),
                       None, None, None, 0.0, "f32")
    assert int(pairs) == 2 * 12 * 4
    np.testing.assert_allclose(y, theirs, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_reference_attention_against_exaone4(kind):
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip(
        "transformers.models.exaone4.modeling_exaone4")
    from transformers.models.exaone4.configuration_exaone4 import (
        Exaone4Config)
    hf = Exaone4Config(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, num_hidden_layers=1, sliding_window=8,
        sliding_window_pattern="LLLG", layer_types=[kind],
        rope_theta=1e6, rms_norm_eps=1e-5, vocab_size=32,
        attention_dropout=0.0)
    hf._attn_implementation = "eager"
    attn = tf.Exaone4Attention(hf, 0).to(torch.float32).eval()
    k = jax.random.split(jax.random.key(14), 8)
    W = {"wq": jax.random.normal(k[0], (32, 32)) * 0.3,
         "wk": jax.random.normal(k[1], (32, 16)) * 0.3,
         "wv": jax.random.normal(k[2], (32, 16)) * 0.3,
         "wo": jax.random.normal(k[3], (32, 32)) * 0.3,
         "q_norm": 1 + 0.1 * jax.random.normal(k[4], (8,)),
         "k_norm": 1 + 0.1 * jax.random.normal(k[5], (8,))}
    with torch.no_grad():
        for proj, n in zip(("q_proj", "k_proj", "v_proj", "o_proj"),
                           wm.ATTENTION):
            getattr(attn, proj).weight.copy_(
                torch.tensor(np.asarray(W[n]).T))
        attn.q_norm.weight.copy_(torch.tensor(np.asarray(W["q_norm"])))
        attn.k_norm.weight.copy_(torch.tensor(np.asarray(W["k_norm"])))
    S = 24
    x = np.asarray(jax.random.normal(k[6], (1, S, 32)))
    pos = jnp.arange(S)[None]
    sliding = kind.startswith("sliding")
    # transformers builds the mask outside the module: causal, and
    # within the window in a sliding layer (key j for query i iff
    # 0 <= i - j < window)
    i, j = np.arange(S)[:, None], np.arange(S)[None]
    seen = (j <= i) & ((i - j < 8) if sliding else True)
    mask = torch.tensor(np.where(seen, 0.0, -1e30)[None, None],
                        dtype=torch.float32)
    inv = 1.0 / (1e6 ** (np.arange(0, 8, 2) / 8))
    ang = np.arange(S)[:, None] * inv[None]
    emb = np.concatenate([ang, ang], -1)[None]
    cos, sin = (torch.tensor(f(emb), dtype=torch.float32)
                for f in (np.cos, np.sin))
    with torch.no_grad():
        theirs = attn(torch.tensor(x), (cos, sin), mask)[0].numpy()
    h = jnp.asarray(x)
    q = (h @ W["wq"]).reshape(1, S, 4, 8)
    kk = (h @ W["wk"]).reshape(1, S, 2, 8)
    v = (h @ W["wv"]).reshape(1, S, 2, 8)
    q = ref.dd.rms_norm(q, W["q_norm"], 1e-5)
    kk = ref.dd.rms_norm(kk, W["k_norm"], 1e-5)
    if sliding:
        q, kk = ref.dd.rope(q, pos, 1e6), ref.dd.rope(kk, pos, 1e6)
    o = ref.attention(q, kk, v, pos, None,
                      8 if sliding else ref.NO_WINDOW)
    mine = o.reshape(1, S, 32) @ W["wo"]
    np.testing.assert_allclose(mine, theirs, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# what the refactor left alone, and what refuses
# ---------------------------------------------------------------------------

def test_mistral_tree_and_scope_table_are_the_parents():
    """The fixture was written by the parent commit's code (PR 25) for
    a tiny Mistral LoRA step: leaf names and shapes, and the op_name of
    every instruction of the compiled step. The prologue, the per-kind
    attention and the routed layer changed nothing of either."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    from gke_ray_train_tpu.train import (
        LoraConfig, make_optimizer, make_train_state, make_train_step)
    with open(os.path.join(HERE, "fixtures",
                           "mistral_tiny_step_parent.json")) as f:
        parent = json.load(f)
    cfg = ModelConfig(
        name="mistral-tiny", block_pattern=("sliding",), rope_theta=10000.0,
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq_len=32, sliding_window=16, dtype="float32",
        param_dtype="float32", remat=True)
    opt = make_optimizer(1e-3)
    lora = LoraConfig(r=4, alpha=8)
    state = make_train_state(cfg, opt, jax.random.key(0), lora_cfg=lora)
    step = make_train_step(cfg, opt, lora_cfg=lora, grad_accum=2,
                           donate=False)
    batch = {"inputs": jnp.zeros((4, 16), jnp.int32),
             "targets": jnp.zeros((4, 16), jnp.int32),
             "weights": jnp.ones((4, 16), jnp.float32)}
    tree = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): [list(x.shape), str(x.dtype)]
            for path, x in jax.tree_util.tree_flatten_with_path(
                state.params)[0]}
    assert tree == parent["params"]
    table = obs_trace.scope_table(
        step.lower(state, batch).compile().as_text())
    assert table == parent["scope_table"]


def test_serving_refuses_the_configuration_by_name():
    from gke_ray_train_tpu.models import kvcache
    from gke_ray_train_tpu.serve.engine import BatchEngine
    cfg = k_exaone_236b(n_layers=8, vocab_size=64, d_model=32, d_ff=64,
                        n_heads=2, n_kv_heads=2, head_dim=16,
                        expert_d_ff=16, n_experts=16, expert_top_k=4)
    for call in (lambda: kvcache.init_cache(cfg, 1, 16),
                 lambda: BatchEngine({}, cfg)):
        with pytest.raises(NotImplementedError) as e:
            call()
        for what in ("k-exaone-236b", "multi-token-prediction",
                     "cache per attention kind"):
            assert what in str(e.value)


def test_preset_counts_and_the_share():
    cfg = k_exaone_236b()
    assert cfg.param_count() == pytest.approx(236.6e9, rel=2e-3)
    assert cfg.active_param_count() == pytest.approx(23.7e9, rel=2e-3)
    assert (cfg.prologue_layers, cfg.n_repeats) == (4, 11)
    assert [cfg.mlp_kind(i) for i in (0, 1, 47)] == ["dense", "moe", "moe"]
    from gke_ray_train_tpu.models.config import preset_for_model_id
    share = preset_for_model_id(
        "LGAI-EXAONE/K-EXAONE-236B-A23B", n_layers=8, vocab_size=19200,
        experts_held=(0, 16))
    # attention 8 x 113.2 M, the dense MLP 339.7 M, seven sparse layers
    # of 16 experts and a shared one (17 x 37.7 M) and a router, both
    # ends of the vocabulary slice
    assert share.param_count() == pytest.approx(5.98e9, rel=2e-3)
    # a token meets one routed expert here on average (8 x 16 / 128)
    assert share.active_param_count() == pytest.approx(2.016e9, rel=2e-3)
    from gke_ray_train_tpu.train.metrics import train_flops_per_token
    assert train_flops_per_token(share, 8192, trainable="lora") \
        == pytest.approx(4 * 2.016e9 + 12 * 8 * 8192 * 8192 * 0.5, rel=2e-3)


def test_quantised_init_streams_the_banks_expert_by_expert():
    """`make_train_state`'s QLoRA base for the routed layer: every
    frozen projection and every held expert is a QTensor made slice by
    slice (no bf16 tree first), router and bias stay plain."""
    from gke_ray_train_tpu.models.qinit import init_quantized_params
    from gke_ray_train_tpu.models.transformer import param_specs
    from gke_ray_train_tpu.ops.quant import is_qtensor
    cfg = drv.model_config(small_config(), dtype="float32",
                           param_dtype="float32", attn_impl="xla",
                           max_seq_len=64)
    params = init_quantized_params(cfg, jax.random.key(0))
    assert jax.tree.structure(
        jax.tree.map(lambda x: 0, params, is_leaf=is_qtensor)) \
        == jax.tree.structure(param_specs(cfg))
    dense, sparse = params["prologue"][0], params["blocks"][3]
    assert dense["w_gate"].codes.shape == (1, 64, 128)
    assert sparse["w_gate"].codes.shape == (1, 4, 64, 64)
    assert sparse["w_down"].scales.shape == (1, 4, 1, 64)
    assert sparse["shared_up"].codes.shape == (1, 64, 64)
    assert not is_qtensor(sparse["router"]) \
        and sparse["router"].shape == (1, 64, 16) \
        and sparse["router_bias"].shape == (1, 16)
