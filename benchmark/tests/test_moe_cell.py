"""The routed decoder's cell (``kexaone236b_ep8_l8.qlora_sft_packed_8k``)
at tiny widths with the look for a chip skipped: a sound run is correct;
half a batch left out, and a copy of the routed layer that drops, are
not; its configuration file keeps the published widths; its readers and
its arithmetic are exact on synthetic facts.
"""

import time

import pytest

from benchmark import flops_moe, run
from benchmark import harness as hs
from benchmark.readers import (
    flash_kind_roofline, moe_experts_roofline, moe_scope_share,
    step_mfu_moe_train)
from benchmark.rehearse.exaone_tiny import CELL, shrink


def drive(seconds=1.0):
    out = run.run_cell(CELL, seed=2 ** 31 + 7, seconds=seconds, trace=False,
                       require_chip=False, t_start=time.perf_counter(),
                       override=shrink)
    return out["result"], out["checks"]


def test_a_sound_run_is_correct():
    result, checks = drive()
    assert result["correct"], checks
    assert set(checks) == {"grad_gap", "change_gap", "pairs_gap"}
    assert checks["pairs_gap"][0] == 0.0
    assert result["failed"] == 0
    assert {"train_tok_s_chip", "setup_s"} <= set(result["metrics"])


def test_half_of_the_batch_left_out(monkeypatch):
    import jax.numpy as jnp

    import gke_ray_train_tpu.train as train_pkg
    real_factory = train_pkg.make_train_step

    def factory(*args, **kwargs):
        real = real_factory(*args, **kwargs)

        def step(state, batch):
            rows = batch["weights"].shape[0]
            keep = (jnp.arange(rows) < rows // 2)[:, None]
            return real(state, dict(batch, weights=jnp.where(
                keep, batch["weights"], 0.0)))
        return step
    monkeypatch.setattr(train_pkg, "make_train_step", factory)
    result, checks = drive()
    assert not result["correct"], checks


def test_a_layer_that_drops_is_not_correct(monkeypatch):
    """The routed layer with a pair buffer a quarter of the positions:
    the pairs past it are dropped, the program says so
    (``moe_pairs_dropped``: the run counts as failed operations) and
    its pairs and gradients part from the reference's."""
    from gke_ray_train_tpu.ops import moe
    real = moe.routed_experts

    def dropping(x, lp, cfg, dtype, valid=None, buffer_rows=None):
        return real(x, lp, cfg, dtype, valid=valid,
                    buffer_rows=x.shape[0] * x.shape[1] // 4)
    monkeypatch.setattr(moe, "routed_experts", dropping)
    result, checks = drive()
    assert not result["correct"], checks
    assert checks["pairs_gap"][0] > checks["pairs_gap"][1]
    assert result["failed"] > 0


# ---------------------------------------------------------------------------
# the configuration file
# ---------------------------------------------------------------------------

def test_configuration_keeps_the_published_widths():
    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    files = hs.cell_files(bench, hs.find_cell(bench, CELL))
    c = files["config"]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (6144, 64, 8, 128)
    assert (c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["num_shared_experts"]) \
        == (18432, 2048, 8, 1)
    assert (c["router_outputs"], c["sliding_window"],
            c["sliding_window_pattern"]) == (128, 128, "LLLG")
    assert (c["routed_scaling_factor"], c["scoring_func"],
            c["norm_topk_prob"], c["first_k_dense_replace"]) \
        == (2.5, "sigmoid", True, 1)
    assert sorted(c["reduced"]) == ["num_experts", "num_hidden_layers",
                                    "num_nextn_predict_layers",
                                    "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["experts_held"],
            c["vocab_size"], c["num_nextn_predict_layers"]) \
        == (8, 16, [0, 16], 19200, 0)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                              "vocab_size": 153600,
                              "num_nextn_predict_layers": 1}
    # the nested groups are the source's, whole; the first 8 are run
    assert len(c["layer_types"]) == len(c["mlp_layer_types"]) == 48
    assert c["layer_types"][:8] == (["sliding_attention"] * 3
                                    + ["full_attention"]) * 2
    assert c["mlp_layer_types"][:8] == ["dense"] + ["sparse"] * 7
    assert set(c["assumed"]) >= {"norm_placement", "qk_norm_and_rotary",
                                 "selection_bias", "weights"}
    assert "8 chips share each layer" in c["deployment"]
    # floors of a cut: a whole period, 8 experts, an eighth of the ids
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= 153600
    mix = files["mix"]
    assert mix["kind"] == "train_moe" and mix["job"]["PACKING"]
    assert (mix["job"]["PER_DEVICE_TRAIN_BATCH_SIZE"]
            * mix["job"]["GRADIENT_ACCUMULATION_STEPS"]
            * mix["job"]["MAX_SEQ_LENGTH"]) == 16384


def test_the_mix_fills_whole_rows():
    from benchmark import traffic
    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    rows = hs.cell_files(bench, hs.find_cell(bench, CELL))["mix"]["rows"]
    sizes = traffic.balanced_groups(
        traffic.quantile_sizes(rows["length"], rows["distinct"]),
        rows["docs_per_row"]).sum(1)
    assert sizes.min() == 7912 and sizes.max() == 8064
    assert sizes.sum() / (8 * 8192) == pytest.approx(0.974, abs=1e-3)


# ---------------------------------------------------------------------------
# arithmetic and readers on synthetic facts
# ---------------------------------------------------------------------------

DIMS = {"vocab": 100, "hidden": 8, "layers": 2, "heads": 2, "kv_heads": 1,
        "head_dim": 4, "ff": 16, "expert_ff": 4, "experts": 8, "held": 2,
        "held_lo": 0, "top_k": 2, "shared": 1, "dense_layers": 1,
        "layers_published": 2}
KINDS = [("sliding", "dense"), ("full", "sparse")]
ATTN = 8 * 8 + 2 * 8 * 4 + 8 * 8            # q, k, v, o
TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def test_arithmetic_counts_what_a_token_meets():
    frozen = 8 * 100 + 2 * ATTN + 3 * 8 * 16 + 8 * 8 + 3 * 8 * 4
    assert flops_moe.frozen_params(DIMS, KINDS) == frozen
    assert flops_moe.expert_params(DIMS) == 3 * 8 * 4
    # rank 1: attention 16 + 12 + 12 + 16 a layer; the dense layer's MLP
    # 3 x 24; the sparse layer's shared expert 3 x 12
    lora = 2 * 56 + 72 + 36
    assert flops_moe.lora_params(DIMS, KINDS, 1, TARGETS) == lora
    assert flops_moe.attention_pairs([3, 5]) == 6 + 15
    # window 2: 1 + 2 + 2 and 1 + 2 + 2 + 2 + 2
    assert flops_moe.attention_pairs([3, 5], window=2) == 5 + 9
    need = flops_moe.train_flops(DIMS, KINDS, [3, 5], held_pairs=7,
                                 lora_rank=1, lora_targets=TARGETS,
                                 window=2)
    assert need == (4 * frozen + 6 * lora) * 8 + 4 * 96 * 7 \
        + 12 * 8 * ((5 + 9) + (6 + 15))


def facts(ops, **work):
    return {"dims": DIMS, "peaks": {"flops_bf16": 1e6,
                                    "hbm_bytes_per_s": 1e9},
            "chips": 1, "t0": 0.0, "window_s": 10.0,
            "trace": {"devices": 1, "op_time": {}, "op_count": {}},
            "scoped_ops": ops, "notes": [],
            "work": dict({"steps": 2, "step_docs": [[3, 5], [4, 4]],
                          "step_pairs": [7.0, 9.0], "micro_steps": 2,
                          "rows_per_call": 1, "seq": 8, "window": 2,
                          "lora_rank": 1, "lora_targets": list(TARGETS),
                          "layer_kinds": KINDS,
                          "step_times": [4.0, 8.0]}, **work)}


def test_step_mfu_counts_pairs_and_window():
    f = facts([])
    need = sum(flops_moe.train_flops(
        DIMS, KINDS, docs, held_pairs=p, lora_rank=1, lora_targets=TARGETS,
        window=2) for docs, p in zip([[3, 5], [4, 4]], [7.0, 9.0]))
    assert step_mfu_moe_train.read(f) == pytest.approx(
        100 * need / (10.0 * 1e6))
    # a traced run: the steps that ended before the profiler started
    f["trace_window"] = (5.0, 9.0)
    first = flops_moe.train_flops(DIMS, KINDS, [3, 5], held_pairs=7.0,
                                  lora_rank=1, lora_targets=TARGETS,
                                  window=2)
    assert step_mfu_moe_train.read(f) == pytest.approx(
        100 * first / (4.0 * 1e6))
    assert step_mfu_moe_train.read(facts([], step_pairs=[])) is None


OPS = [
    (2.0, 4, "jit(step)/jvp(moe/experts)/pallas_call", "moe/experts"),
    (1.0, 4, "jit(step)/jvp(moe/experts)/mul", "moe/experts"),
    (5.0, 4, "jit(step)/rematted_computation/moe/experts/x", "moe/experts"),
    (0.5, 4, "jit(step)/jvp(moe/route)/top_k", "moe/route"),
    (1.5, 4, "jit(step)/transpose(jvp(moe/combine))/gather", "moe/combine"),
    (2.0, 4, "jit(step)/jvp(moe/shared/base)/dot", "moe/shared/base"),
    (3.0, 4, "jit(step)/jvp(unembed)/dot", "unembed"),
    (5.0, 8, "jit(step)/jvp(attn/qkv/base)/dot", "attn/qkv/base"),
]


def test_moe_shares():
    f = facts(OPS)
    assert moe_scope_share.read(f) == pytest.approx(100 * 12.0 / 20.0)
    assert moe_scope_share.read(
        f, stages=["route", "dispatch", "combine"]) \
        == pytest.approx(100 * 2.0 / 20.0)
    assert moe_scope_share.read(facts(None)) is None


def test_moe_experts_roofline_is_forward_only_and_by_pairs():
    f = facts(OPS)
    # 4 forward passes of the head; 8 pairs a step over 2 micro-passes;
    # 2 FLOP a pair and expert weight; 3 s of forward time under the scope
    least = 4 * (2 * 96 * 4.0) / 1e6
    assert moe_experts_roofline.read(f) == pytest.approx(100 * least / 3.0)
    assert moe_experts_roofline.read(facts(OPS, step_pairs=[])) is None


def test_flash_kind_roofline_bills_each_kind_its_pairs(monkeypatch):
    from benchmark.readers import program_trace as pt

    class Record:
        scope_tables = {"step": {
            "flash_fwd.1": "jit(step)/jvp(attn/core/window)/pallas_call",
            "flash_fwd.2": "jit(step)/jvp(attn/core/full)/pallas_call",
            "flash_dq.1": "jit(s)/transpose(jvp(attn/core/full))/pallas_call",
        }}

    class Program:
        RECORD = Record()

        @staticmethod
        def scope_path(op):
            from gke_ray_train_tpu.obs.trace import scope_path
            return scope_path(op)
    monkeypatch.setattr(pt, "program", lambda: Program)
    f = facts(OPS)
    f["trace"].update(
        op_time={"%flash_fwd.1 = (bf16[1,2,8,4]) custom-call()": 1.0,
                 "%flash_fwd.2 = (bf16[1,2,8,4]) custom-call()": 2.0,
                 "%flash_dq.1 = bf16[1,2,8,4] custom-call()": 4.0,
                 "%fusion.9 = bf16[8] fusion()": 9.0},
        op_count={"%flash_fwd.1 = (bf16[1,2,8,4]) custom-call()": 4,
                  "%flash_fwd.2 = (bf16[1,2,8,4]) custom-call()": 4,
                  "%flash_dq.1 = bf16[1,2,8,4] custom-call()": 4})
    docs = [3, 5, 4, 4]
    rows = 4                                  # 2 steps x 2 micro x 1 row
    window = flops_moe.flash_call(
        DIMS, 1, 8, flops_moe.attention_pairs(docs, 2) / rows)
    full = flops_moe.flash_call(
        DIMS, 1, 8, flops_moe.attention_pairs(docs) / rows)

    def least(need, kernel):
        return 4 * max(need[kernel]["flops"] / 1e6,
                       need[kernel]["bytes"] / 1e9)
    assert flash_kind_roofline.read(f, kind="window") == pytest.approx(
        100 * least(window, "flash_fwd") / 1.0)
    assert flash_kind_roofline.read(f, kind="full") == pytest.approx(
        100 * (least(full, "flash_fwd") + least(full, "flash_dq")) / 6.0)
    f["trace"]["op_time"] = {"%fusion.9 = bf16[8] fusion()": 9.0}
    assert flash_kind_roofline.read(f, kind="full") is None
