"""The hybrid state-space / attention routed decoder's cell
(``granite4hsmall_ep4_l20.qlora_sft_packed_8k_ssm``) at tiny widths with
the look for a chip skipped: a sound run is correct; a state carried
across a document boundary, a conv that reads the previous document's
last three positions, the fp8 control and half a batch are not, each
through the harness's own ``judge`` under the committed limits; its
readers and its arithmetic are exact on synthetic facts, and every new
metric file is wired to a reader that takes its args.
"""

import time

import pytest

from benchmark import flops_ssm, run
from benchmark import harness as hs
from benchmark.readers import (
    nf4_roofline_ssm, ssm_scan_roofline, ssm_scope_share,
    step_mfu_ssm_train)
from benchmark.rehearse.granite_tiny import CELL, shrink

NEW_METRICS = ("step_mfu_ssm.train", "ssm_share.train",
               "ssm_scan_share.train", "ssm_scan_roofline.train",
               "nf4_matmul_roofline_ssm.train")


def drive(seconds=1.0):
    out = run.run_cell(CELL, seed=2 ** 31 + 7, seconds=seconds, trace=False,
                       require_chip=False, t_start=time.perf_counter(),
                       override=shrink)
    return out["result"], out["checks"]


def test_a_sound_run_is_correct():
    result, checks = drive()
    assert result["correct"], checks
    assert set(checks) == {"grad_gap", "change_gap", "grad_dir_gap",
                           "ssm_dir_gap", "pairs_gap"}
    assert checks["pairs_gap"][0] == 0.0
    assert result["failed"] == 0
    assert {"train_tok_s_chip", "setup_s"} <= set(result["metrics"])


def test_state_carried_across_a_document_boundary(monkeypatch):
    """The scan told nothing of the documents: a document's first
    position meets the state the previous one left."""
    from gke_ray_train_tpu.ops import ssm
    real = ssm.ssd_scan

    def carried(x, dt, a, b, c, d, segment_ids, **kw):
        return real(x, dt, a, b, c, d, None, **kw)
    monkeypatch.setattr(ssm, "ssd_scan", carried)
    result, checks = drive()
    assert not result["correct"], checks
    value, limit = checks["ssm_dir_gap"]
    assert value > limit


def test_conv_reads_the_previous_document(monkeypatch):
    """The conv told nothing of the documents: a document's first three
    positions read the last three of the one before."""
    from gke_ray_train_tpu.ops import ssm
    real = ssm.causal_conv
    monkeypatch.setattr(ssm, "causal_conv",
                        lambda x, w, b, segment_ids: real(x, w, b, None))
    result, checks = drive()
    assert not result["correct"], checks
    value, limit = checks["ssm_dir_gap"]
    assert value > limit


def test_control_and_half_a_batch_are_not_correct():
    """The reference in fp8, and the reference fed half of each batch,
    put in the program's place."""
    from benchmark.tools import readings
    ctx = hs.make_ctx(CELL, 2 ** 31 + 7, 0.0, False, require_chip=False,
                      override=shrink)
    facts = hs.driver_of(ctx).run(ctx)
    limits = ctx["limits"]
    assert readings.judged(facts["readings"], limits)["correct"]
    got = {k: readings.judged(r, limits) for k, (r, _) in
           readings.control_readings(
               facts["raw"], [limits["control"], "half_batch"]).items()}
    assert not got["fp8"]["correct"]
    assert {"grad_dir_gap", "ssm_dir_gap"} & set(got["fp8"]["failed"])
    assert got["fp8"]["readings"]["grad_dir_gap"] \
        > 1e3 * facts["readings"]["grad_dir_gap"]
    assert {"grad_dir_gap", "ssm_dir_gap", "pairs_gap"} \
        <= set(got["half_batch"]["failed"])


# ---------------------------------------------------------------------------
# arithmetic and readers on synthetic facts
# ---------------------------------------------------------------------------

DIMS = {"vocab": 100, "hidden": 8, "layers": 3, "heads": 2, "kv_heads": 1,
        "head_dim": 4, "ff": 4, "expert_ff": 4, "shared_ff": 6,
        "experts": 8, "held": 2, "held_lo": 0, "top_k": 3, "shared": 1,
        "dense_layers": 0, "layers_published": 6, "ssm_heads": 4,
        "ssm_head_dim": 4, "ssm_state": 3, "ssm_groups": 1, "ssm_conv": 4,
        "ssm_chunk": 5, "ssm_inner": 16, "ssm_conv_dim": 22}
KINDS = [("mamba", "sparse"), ("full", "sparse"), ("mamba", "sparse")]
MIXER = 8 * (16 + 22 + 4) + 16 * 8          # in_proj, out_proj
ATTN = 8 * 8 + 2 * 8 * 4 + 8 * 8            # q, k, v (one kv head), o
SHARED = 3 * 8 * 6
TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def test_arithmetic_bills_the_mixer_where_it_stands():
    base = 2 * MIXER + ATTN + 3 * SHARED
    assert flops_ssm.base_params(DIMS, KINDS) == base
    assert flops_ssm.frozen_params(DIMS, KINDS) == 8 * 100 + base + 3 * 64
    assert flops_ssm.expert_params(DIMS) == 3 * 8 * 4
    # rank 1: (in + out) of the mixer's two, attention's four, the
    # shared expert's three in every layer
    mixer = (8 + 42) + (16 + 8)
    attn = (8 + 8) + 2 * (8 + 4) + (8 + 8)
    shared = 2 * (8 + 6) + (6 + 8)
    assert flops_ssm.lora_params(DIMS, KINDS, 1, TARGETS) \
        == 2 * mixer + attn + 3 * shared
    assert flops_ssm.lora_params(DIMS, KINDS, 1, ("wq",)) \
        == 2 * (8 + 42) + (8 + 8)
    # a position of a layer: H (2 Q P + 4 N P) + 2 Q N G; x, y, B, C, dt
    need = flops_ssm.scan_position(DIMS)
    assert need == {"flops": 4 * (2 * 5 * 4 + 4 * 3 * 4) + 2 * 5 * 3,
                    "bytes": 2.0 * (2 * 16 + 2 * 3 + 4)}
    # the published sizes: 43 ns a position a layer on a v5e
    real = dict(DIMS, ssm_heads=128, ssm_head_dim=64, ssm_state=128,
                ssm_chunk=256)
    assert flops_ssm.scan_position(real) == {"flops": 8454144.0,
                                             "bytes": 33536.0}
    got = flops_ssm.train_flops(DIMS, KINDS, [3, 5], held_pairs=7,
                                lora_rank=1, lora_targets=TARGETS)
    assert got == (4 * flops_ssm.frozen_params(DIMS, KINDS)
                   + 6 * (2 * mixer + attn + 3 * shared)) * 8 \
        + 4 * 96 * 7 + 12 * 8 * 1 * (6 + 15) + 3 * need["flops"] * 8 * 2


def facts(ops, **work):
    return {"dims": DIMS, "peaks": {"flops_bf16": 1e6,
                                    "hbm_bytes_per_s": 1e9},
            "chips": 1, "t0": 0.0, "window_s": 10.0,
            "trace": {"devices": 1, "op_time": {}, "op_count": {}},
            "scoped_ops": ops, "notes": [],
            "spans": {"init_s": 1.0, "warm_build_s": 2.0},
            "work": dict({"steps": 2, "step_docs": [[3, 5], [4, 4]],
                          "step_pairs": [7.0, 9.0], "micro_steps": 2,
                          "rows_per_call": 1, "seq": 8,
                          "lora_rank": 1, "lora_targets": list(TARGETS),
                          "layer_kinds": KINDS,
                          "step_times": [4.0, 8.0]}, **work)}


def test_step_mfu_ssm_counts_pairs_and_the_traced_slice():
    f = facts([])
    need = sum(flops_ssm.train_flops(
        DIMS, KINDS, docs, held_pairs=p, lora_rank=1, lora_targets=TARGETS)
        for docs, p in zip([[3, 5], [4, 4]], [7.0, 9.0]))
    assert step_mfu_ssm_train.read(f) == pytest.approx(
        100 * need / (10.0 * 1e6))
    f["trace_window"] = (5.0, 9.0)
    first = flops_ssm.train_flops(DIMS, KINDS, [3, 5], held_pairs=7.0,
                                  lora_rank=1, lora_targets=TARGETS)
    assert step_mfu_ssm_train.read(f) == pytest.approx(
        100 * first / (4.0 * 1e6))
    assert step_mfu_ssm_train.read(facts([], step_pairs=[])) is None
    # another family's facts give it nothing to read
    other = facts([])
    other["dims"] = {k: v for k, v in DIMS.items() if k != "ssm_heads"}
    assert step_mfu_ssm_train.read(other) is None


OPS = [
    (2.0, 4, "jit(step)/jvp(ssm/in_proj/base)/dot", "ssm/in_proj/base"),
    (1.0, 4, "jit(step)/jvp(ssm/in_proj/lora)/dot", "ssm/in_proj/lora"),
    (0.5, 4, "jit(step)/jvp(ssm/conv)/mul", "ssm/conv"),
    (3.0, 4, "jit(step)/jvp(ssm/scan)/dot", "ssm/scan"),
    (2.5, 4, "jit(step)/rematted_computation/ssm/scan/dot", "ssm/scan"),
    (4.0, 4, "jit(step)/transpose(jvp(ssm/scan))/dot", "ssm/scan"),
    (0.5, 4, "jit(step)/jvp(ssm/gate_norm)/mul", "ssm/gate_norm"),
    (1.5, 4, "jit(step)/jvp(ssm/out_proj/base)/dot", "ssm/out_proj/base"),
    (2.0, 4, "jit(step)/jvp(attn/out/base)/dot", "attn/out/base"),
    (3.0, 4, "jit(step)/jvp(moe/experts)/pallas_call", "moe/experts"),
]


def test_ssm_scope_shares():
    f = facts(OPS)
    assert ssm_scope_share.read(f) == pytest.approx(100 * 15.0 / 20.0)
    assert ssm_scope_share.read(f, stages=["conv", "scan", "gate_norm"]) \
        == pytest.approx(100 * 10.5 / 20.0)
    assert ssm_scope_share.read(facts(None)) is None
    # a program that opens no such scope (the parent's) leaves it out
    assert ssm_scope_share.read(facts(OPS[-2:])) is None


def test_rooflines_bill_the_counted_passes():
    ops = OPS + [
        (0.3, 4, "jit(step)/jvp(unembed)/dot", "unembed"),
        (5.0, 4, "jit(step)/transpose(jvp(attn/out/base))/dot",
         "attn/out/base"),
        (1.0, 4, "jit(step)/rematted_computation/moe/shared/base/dot",
         "moe/shared/base")]
    # 4 passes of 1 row x 8 positions through 2 state-space layers: the
    # larger of operations over peak and bytes over peak a position;
    # forward `ssm/scan` time alone: 3.0
    need = flops_ssm.scan_position(DIMS)
    least = 4 * 8 * 2 * max(need["flops"] / 1e6, need["bytes"] / 1e9)
    assert ssm_scan_roofline.read(facts(ops)) == pytest.approx(
        100 * least / 3.0)
    # forward `base` time: 2 + 1.5 + 2
    base = flops_ssm.base_params(DIMS, KINDS)
    assert nf4_roofline_ssm.read(facts(ops)) == pytest.approx(
        100 * (4 * 2.0 * 8 * base / 1e6) / 5.5)
    for reader in (ssm_scan_roofline, nf4_roofline_ssm):
        # no pass to count, another family's facts, no trace: nothing
        assert reader.read(facts(OPS)) is None
        other = facts(ops)
        other["dims"] = {k: v for k, v in DIMS.items() if k != "ssm_heads"}
        assert reader.read(other) is None
        assert reader.read(facts(None)) is None
    # a program with no scan under its name (the parent's)
    assert ssm_scan_roofline.read(facts(ops[-5:])) is None


def test_new_metrics_are_wired():
    """Every new metric file names a reader that takes its args, and
    BENCHMARK.json lists the cell where the metric holds."""
    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    cell = hs.find_cell(bench, CELL)
    ops = OPS + [(0.3, 4, "jit(step)/jvp(unembed)/dot", "unembed")]
    out = hs.read_metrics(list(NEW_METRICS), facts(ops))
    assert set(out) == set(NEW_METRICS)
    assert all(v["unit"] == "%" for v in out.values())
    wanted = hs.wanted_metrics(bench, cell, True)
    assert set(NEW_METRICS) <= set(wanted)
    assert {"moe_share.train", "moe_dispatch_share.train",
            "moe_experts_roofline.train", "flash_full_roofline.train",
            "proj_share.train", "unscoped_device_share.train",
            "init_s", "warm_build_s"} <= set(wanted)
    assert not {"step_mfu.train", "step_mfu_moe.train",
                "step_mfu_mla.train", "flash_window_roofline.train",
                "nf4_matmul_roofline.train"} & set(wanted)
    assert hs.wanted_metrics(bench, cell, False) == ["train_tok_s_chip",
                                                     "setup_s"]
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] \
                and m["moves"] == "train_tok_s_chip"
