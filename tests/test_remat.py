"""What the block checkpoints keep (models/remat.py, train/remat.py):
the byte arithmetic and the first-fit choice, bitwise equality of a
step with and without kept activations on the layer loops that take a
keep set, the recomputation that a kept name removes from the compiled
step, which device is asked for its limit, and the build's fallback
when the compiler finds no room."""

import dataclasses
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gke_ray_train_tpu.models import remat, tiny
from gke_ray_train_tpu.models.remat import (
    KEEP_ORDER, choose_keep, keep_candidates)
from gke_ray_train_tpu.obs import trace as obs_trace
from gke_ray_train_tpu.perf import cache
from gke_ray_train_tpu.train import remat as step_remat
from gke_ray_train_tpu.train.remat import RematChoice, StepRemat

# the benchmark cell's candidates, in KEEP_ORDER (bytes a device)
CANDS = (("mlp/gate_up", 3758), ("attn/core", 545), ("attn/qkv", 805),
         ("attn/out", 537))


@pytest.mark.parametrize("budget,expected", [
    (500, ()),
    (-3, ()),
    (600, ("attn/core",)),
    (2000, ("attn/core", "attn/qkv", "attn/out")),
    (3800, ("mlp/gate_up",)),
    (4400, ("mlp/gate_up", "attn/core")),
    (6000, KEEP_ORDER[:4]),
    (None, ()),
], ids=["nothing_fits", "negative_budget", "only_attn_core",
        "skips_gate_up_takes_the_smaller", "gate_up_alone",
        "gate_up_then_core_skips_qkv", "all_fit", "no_bytes_limit"])
def test_choose_keep_first_fit(budget, expected):
    assert choose_keep(CANDS, budget) == expected


def _mistral7b(**kw):
    from gke_ray_train_tpu.models.config import ModelConfig
    return ModelConfig(**{**dict(
        name="m", vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=4096, dtype="bfloat16",
        param_dtype="bfloat16"), **kw})


@pytest.mark.parametrize("cfg_kw,kw,expected", [
    # ISSUE 25's table: 2 x 1024 positions a micro-batch, bf16, 32 layers
    ({}, {}, {"mlp/gate_up": 32 * 2 * 58_720_256,
              "attn/core": 32 * (16_777_216 + 262_144),
              "attn/qkv": 32 * 25_165_824,
              "attn/out": 32 * 16_777_216}),
    # heads and d_ff divide over the tensor-parallel axis, d_model not
    ({}, {"model": 4}, {"mlp/gate_up": 32 * 2 * 58_720_256 // 4,
                        "attn/core": 32 * (16_777_216 + 262_144) // 4,
                        "attn/qkv": 32 * 25_165_824 // 4,
                        "attn/out": 32 * 16_777_216}),
    # the dense attention path has nothing named attn/core
    ({}, {"flash": False}, {"mlp/gate_up": 32 * 2 * 58_720_256,
                            "attn/qkv": 32 * 25_165_824,
                            "attn/out": 32 * 16_777_216}),
    # MoE blocks: only the attention tensors are candidates
    ({"n_experts": 8, "expert_top_k": 2}, {},
     {"attn/core": 32 * (16_777_216 + 262_144),
      "attn/qkv": 32 * 25_165_824, "attn/out": 32 * 16_777_216}),
], ids=["cell", "model_axis_4", "dense_attention", "moe"])
def test_keep_candidates_bytes(cfg_kw, kw, expected):
    got = keep_candidates(_mistral7b(**cfg_kw), 2, 1024, **kw)
    assert dict(got) == expected
    assert [n for n, _ in got] == [n for n in KEEP_ORDER if n in expected]


def _kexaone_share(**kw):
    from gke_ray_train_tpu.models.config import k_exaone_236b
    return k_exaone_236b(**{**dict(
        n_layers=8, vocab_size=19200, experts_held=(0, 16), n_mtp_layers=0,
        max_seq_len=8192, dtype="bfloat16", param_dtype="bfloat16"), **kw})


def _glm_share(**kw):
    from gke_ray_train_tpu.models.config import glm_4_7_flash
    return glm_4_7_flash(**{**dict(
        vocab_size=38720, experts_held=(0, 16), n_mtp_layers=0,
        max_seq_len=8192, dtype="bfloat16", param_dtype="bfloat16"), **kw})


# arguments: ``compiled.memory_analysis().argument_size_in_bytes`` of the
# cell's step on the chip (PERF.md, PR 25 / PR 26: NF4 codes a byte each;
# PR 29: two a byte)
@pytest.mark.parametrize("make_cfg,rows,seq,lora_bytes,ws_gb,cases", [
    (_mistral7b, 2, 1024, 671_088_640, 3.251, [
        (9_953_715_712, ("mlp/gate_up",)),
        (6_464_054_784, ("mlp/gate_up", "attn/core", "attn/qkv",
                         "attn/out"))]),
    (_kexaone_share, 1, 8192, 150_994_944, 7.007, [
        (7_033_021_440, ("mlp/gate_up", "attn/core", "attn/out",
                         "moe/shared")),
        (4_164_117_504, ("mlp/gate_up", "attn/core", "attn/qkv",
                         "attn/out", "moe/shared"))]),
    # PR 30: XLA's peak with nothing kept is 12.53 GB there (6.02 beside
    # the arguments); with these two names 16.01 of 16.91, and with
    # `attn/latent` as well the compiler refuses the step by 128 MiB
    (_glm_share, 1, 8192, 472_563_712, 6.264, [
        (6_509_583_628, ("mlp/gate_up", "attn/core"))]),
], ids=["dense_cell", "routed_cell", "latent_cell"])
def test_working_set_and_budget_of_the_cell(make_cfg, rows, seq,
                                            lora_bytes, ws_gb, cases):
    """The arithmetic PERF.md (PR 25) sets beside XLA's memory analysis
    of the 7B QLoRA step: 3.25 GB against 3.08 GB, and with it both
    benchmark cells' choices on a v5e chip, with the frozen base's codes
    a byte each and at two a byte: the room that the codes give back
    goes to the attention's names."""
    limit = 16_909_336_064
    cfg = make_cfg()
    ws = remat.working_set_bytes(cfg, rows, seq, model=1,
                                 trainable_bytes=lora_bytes,
                                 trainable_full_bytes=lora_bytes,
                                 cast_bytes=lora_bytes // 2)
    assert ws == pytest.approx(ws_gb * 1e9, rel=1e-3)
    candidates = keep_candidates(cfg, rows, seq)
    for arguments, expected in cases:
        budget = limit - arguments - ws - step_remat.RESERVE_BYTES
        assert choose_keep(candidates, budget,
                           peak_share=remat.KEPT_PEAK_SHARE) == expected
    # charged in full, the dense cell's old budget would have kept the
    # three smaller names
    if make_cfg is _mistral7b:
        budget = limit - cases[0][0] - ws - step_remat.RESERVE_BYTES
        assert choose_keep(candidates, budget) == (
            "attn/core", "attn/qkv", "attn/out")


@pytest.mark.parametrize("kw,grows", [
    ({"rows": 4}, True), ({"seq": 2048}, True),
    ({"trainable_bytes": 2 * 671_088_640}, True),
    ({"cast_bytes": 1}, True),
    ({"vocab_size": 128_256}, False),   # the block's backward stays fuller
    ({"vocab_size": 1_000_000}, True),
], ids=["rows", "seq", "trainable", "cast", "vocab_128k", "vocab_1m"])
def test_working_set_follows_what_it_is_told(kw, grows):
    base = dict(rows=2, seq=1024, trainable_bytes=671_088_640,
                cast_bytes=0, vocab_size=32000)
    def ws(d):
        return remat.working_set_bytes(
            _mistral7b(vocab_size=d["vocab_size"]), d["rows"], d["seq"],
            model=1, trainable_bytes=d["trainable_bytes"],
            trainable_full_bytes=d["trainable_bytes"],
            cast_bytes=d["cast_bytes"])
    assert (ws({**base, **kw}) > ws(base)) is grows


def test_shard_bytes_counts_one_device(devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices).reshape(2, 4), ("data", "fsdp"))
    tree = {"a": jax.ShapeDtypeStruct((8, 64), jnp.float32,
                                      sharding=NamedSharding(
                                          mesh, P("fsdp", None))),
            "b": jax.ShapeDtypeStruct((16,), jnp.bfloat16,
                                      sharding=NamedSharding(mesh, P())),
            "c": jax.ShapeDtypeStruct((3, 5), jnp.int8),
            # NF4 codes: two a byte on the device, rounded up a leaf
            "d": jax.ShapeDtypeStruct((8, 32), jnp.uint4,
                                      sharding=NamedSharding(
                                          mesh, P("fsdp", None))),
            "e": jax.ShapeDtypeStruct((3, 5), jnp.uint4)}
    assert step_remat.shard_bytes(tree) == (
        2 * 64 * 4 + 16 * 2 + 15 + 2 * 32 // 2 + 8)
    assert step_remat.shard_bytes(tree, whole=True) == (
        8 * 64 * 4 + 16 * 2 + 15 + 8 * 32 // 2 + 8)
    # as if every leaf were of one type: a byte type bills bytes again
    assert step_remat.shard_bytes(tree["e"], dtype=jnp.bfloat16) == 30


def test_donation_of_a_quantised_state_is_counted_in_bits(devices):
    """``assert_state_donation`` sets XLA's aliased bytes against the
    state's: the NF4 base is most of a QLoRA state, and XLA counts its
    codes two a byte."""
    from gke_ray_train_tpu.perf.costs import assert_state_donation
    from gke_ray_train_tpu.train import make_train_step
    cfg, opt, state, batch, step_kw = _setup("qlora_xla", devices)
    step = make_train_step(cfg, opt, **{**step_kw, "donate": True})
    compiled = step.lower(state, batch).compile()
    held = step_remat.shard_bytes(state)
    assert held < sum(x.nbytes for x in jax.tree.leaves(state))
    # the assertion divides the state over every device of the process;
    # this state lives whole on one
    aliased = assert_state_donation(compiled, state,
                                    min_frac=0.95 * len(jax.devices()))
    assert 0.95 * held <= aliased <= 1.05 * held


# ---------------------------------------------------------------------------
# a kept value and a recomputed one are the same bits
# ---------------------------------------------------------------------------

def _tokens(rows, seq, vocab=128):
    toks = np.random.default_rng(0).integers(1, vocab, (rows, seq)
                                             ).astype(np.int32)
    return {"inputs": jnp.asarray(toks),
            "targets": jnp.asarray(np.roll(toks, -1, 1)),
            "weights": jnp.ones((rows, seq), jnp.float32)}


def _setup(kind, devices):
    """(cfg, optimizer, state, batch, make_train_step kwargs)."""
    from gke_ray_train_tpu.models.transformer import init_params
    from gke_ray_train_tpu.ops.quant import quantize_params
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    from gke_ray_train_tpu.plan import ExecutionPlan
    from gke_ray_train_tpu.train import (
        LoraConfig, make_optimizer, make_train_state)
    kw = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
              n_kv_heads=1, d_ff=64, max_seq_len=128, remat=True)
    opt = make_optimizer(1e-2)
    step_kw, mesh, lora_cfg, params = {"grad_accum": 2}, None, None, None
    if kind.startswith("qlora"):
        cfg = (tiny(**kw, attn_impl="xla", qk_norm=True)
               if kind == "qlora_qk_norm"
               else tiny(**kw, attn_impl=kind.split("_")[1]))
        lora_cfg = LoraConfig(r=4, alpha=8)
        params = quantize_params(init_params(cfg, jax.random.key(0)),
                                 "nf4")
    elif kind == "full_ft":
        cfg = tiny(**kw)
    elif kind == "manual_overlap":
        cfg = tiny(**{**kw, "n_kv_heads": 2})
        mesh = build_mesh(MeshConfig(data=1, fsdp=8), devices)
        step_kw["plan"] = ExecutionPlan.from_kwargs(
            data=1, fsdp=8, overlap="manual", grad_accum=2,
            aot_train_step=False)
    state = make_train_state(cfg, opt, jax.random.key(1), mesh=mesh,
                             lora_cfg=lora_cfg, params=params)
    if lora_cfg is not None:
        # B starts at zero, which would zero the gradient of every A
        state = state._replace(
            lora=jax.tree.map(lambda x: x + 0.01, state.lora))
    step_kw.update(mesh=mesh, lora_cfg=lora_cfg, donate=False)
    return cfg, opt, state, _tokens(16, 128), step_kw


@pytest.mark.parametrize("kind", ["qlora_xla", "qlora_flash", "full_ft",
                                  "manual_overlap", "qlora_qk_norm"])
def test_kept_activations_are_bitwise_the_recomputed_ones(kind, devices):
    """One optimizer step: the loss, the gradient norm and every updated
    leaf (so every gradient) are equal bit for bit with nothing kept and
    with every name kept, on the two layer loops that take a keep set
    (the pipelined one keeps nothing)."""
    from gke_ray_train_tpu.train import make_train_step
    cfg, opt, state, batch, step_kw = _setup(kind, devices)
    outs = []
    for keep in ((), KEEP_ORDER):
        new, metrics = make_train_step(cfg, opt, remat_keep=keep,
                                       **step_kw)(state, batch)
        trained = new.lora if new.lora is not None else new.params
        outs.append(jax.device_get((metrics["loss"], metrics["grad_norm"],
                                    trained, new.opt_state)))
    assert float(outs[0][0]) > 0 and float(outs[0][1]) > 0
    same = jax.tree.map(lambda a, b: bool(np.array_equal(a, b)), *outs)
    assert jax.tree.all(same), same


# ---------------------------------------------------------------------------
# the recomputation a kept name removes
# ---------------------------------------------------------------------------

def _recomputed_matmuls(compiled):
    """Scope paths of the matmuls the compiled step runs again in its
    backward pass, from the program's own scope table."""
    table = obs_trace.scope_table(compiled.as_text())
    return {obs_trace.scope_path(op) for op in table.values()
            if "rematted_computation" in op and op.endswith("dot_general")}


def test_scope_table_loses_the_recomputed_gate_up_matmul(devices):
    from gke_ray_train_tpu.train import make_train_step
    cfg, opt, state, batch, step_kw = _setup("qlora_xla", devices)

    def recomputed(keep):
        step = make_train_step(cfg, opt, remat_keep=keep, **step_kw)
        return _recomputed_matmuls(step.lower(state, batch).compile())

    nothing_kept = recomputed(())
    assert {"mlp/gate_up/base", "attn/qkv/base",
            "attn/out/base"} <= nothing_kept
    gate_up_kept = recomputed(("mlp/gate_up",))
    assert "mlp/gate_up/base" not in gate_up_kept
    assert {"attn/qkv/base", "attn/out/base"} <= gate_up_kept
    assert not {p for p in recomputed(KEEP_ORDER) if p.endswith("/base")}


@pytest.mark.parametrize("kind", ["qlora_xla", "qlora_qk_norm"])
def test_attn_qkv_kept_spares_the_three_projections(kind, devices):
    """The name sits where keeping it is worth its bytes: on q and k
    after rope, or, where a norm follows the projections, on their
    outputs (the norm's backward reads them)."""
    from gke_ray_train_tpu.train import make_train_step
    cfg, opt, state, batch, step_kw = _setup(kind, devices)

    def recomputed(keep):
        step = make_train_step(cfg, opt, remat_keep=keep, **step_kw)
        return _recomputed_matmuls(step.lower(state, batch).compile())

    assert "attn/qkv/base" in recomputed(())
    kept = recomputed(("attn/qkv",))
    assert "attn/qkv/base" not in kept and "attn/out/base" in kept


# ---------------------------------------------------------------------------
# the limit: asked of a device of this process, errors not swallowed
# ---------------------------------------------------------------------------

def _device(limit=None, error=None):
    def memory_stats():
        if error is not None:
            raise error
        return None if limit is None else {"bytes_limit": limit}
    return types.SimpleNamespace(memory_stats=memory_stats)


def test_limit_is_asked_of_a_local_device():
    """On a slice of several hosts the mesh's first device belongs to
    one of them; every host asks a device of its own, and so reaches
    the same keep set."""
    remote = _device(error=jax.errors.JaxRuntimeError(
        "INVALID_ARGUMENT: MemoryStats is only supported for addressable "
        "PjRt devices."))
    local = _device(limit=16_909_336_064)
    mesh = types.SimpleNamespace(devices=np.array([remote, local]),
                                 local_devices=[local])
    assert step_remat.device_bytes_limit(mesh) == 16_909_336_064


@pytest.mark.parametrize("device,expected", [
    (_device(), None),                      # XLA:CPU reports no stats
    (_device(limit=0), None),
    (_device(error=jax.errors.JaxRuntimeError(
        "INVALID_ARGUMENT: MemoryStats is only supported for addressable "
        "PjRt devices.")), None),           # a described topology
    (_device(error=jax.errors.JaxRuntimeError("INTERNAL: lost the chip")),
     jax.errors.JaxRuntimeError),
    (_device(error=KeyError("boom")), KeyError),
], ids=["no_stats", "zero_limit", "compile_only_client", "runtime_error",
        "other_error"])
def test_a_device_without_a_limit_and_one_that_fails(device, expected):
    mesh = types.SimpleNamespace(local_devices=[device])
    if expected is None:
        assert step_remat.device_bytes_limit(mesh) is None
    else:
        with pytest.raises(expected):
            step_remat.device_bytes_limit(mesh)


def test_the_cpu_reports_no_limit(devices):
    mesh = jax.sharding.Mesh(np.array(devices[:1]), ("data",))
    assert step_remat.device_bytes_limit(mesh) is None
    assert step_remat.device_bytes_limit(None) is None


# ---------------------------------------------------------------------------
# the build: one lower on the path that fits, the compiler as the judge
# ---------------------------------------------------------------------------

@pytest.fixture
def record():
    obs_trace.RECORD.clear()
    yield obs_trace.RECORD
    obs_trace.RECORD.clear()


def _aot_build(monkeypatch, devices, *, limit, stub=None, sidecar=None):
    from gke_ray_train_tpu.train import make_train_step
    cfg, opt, state, batch, step_kw = _setup("qlora_xla", devices)
    monkeypatch.setattr(step_remat, "device_bytes_limit",
                        lambda mesh: limit)
    fn = make_train_step(cfg, opt, **step_kw)
    sizer = fn.remat if stub is None else stub(fn.remat)
    built = sizer.build(fn, state, batch, label="tiny remat",
                        sidecar=sidecar)
    new, metrics = built(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    return built


def _spans(record, name):
    return [s for s in record.spans if s["name"] == name]


def test_no_bytes_limit_keeps_nothing(monkeypatch, devices, record):
    """XLA:CPU reports no limit (every tier-1 test): today's program."""
    built = _aot_build(monkeypatch, devices, limit=None)
    assert built.info["remat_keep"] == []
    assert built.info["remat_budget_bytes"] is None
    assert built.info["remat_args_bytes"] is None
    assert built.info["remat_keep_fallback"] is False
    assert len(_spans(record, "step_lower")) == 1


def test_room_keeps_every_name_with_one_lower(monkeypatch, devices,
                                              record):
    built = _aot_build(monkeypatch, devices, limit=1 << 30)
    (span,) = _spans(record, "step_build")
    # the dense attention of the CPU has nothing named attn/core
    assert span["remat_keep"] == built.info["remat_keep"] == [
        "mlp/gate_up", "attn/qkv", "attn/out"]
    assert span["remat_keep_bytes"] == built.info["remat_keep_bytes"] > 0
    assert span["remat_budget_bytes"] >= span["remat_keep_bytes"]
    # what the budget subtracted from the limit: codes at half a byte
    _, _, state, batch, _ = _setup("qlora_xla", devices)
    assert span["remat_args_bytes"] == built.info["remat_args_bytes"] == (
        step_remat.shard_bytes((state, batch)))
    codes = sum(x.size for x in jax.tree.leaves(state.params)
                if x.dtype == jnp.uint4)
    assert codes > 0 and span["remat_args_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves((state, batch))) - codes // 2
    assert span["remat_keep_fallback"] is False
    # the dense-mask attention of the CPU walks no flash grid
    assert span["flash_grid"] == built.info["flash_grid"] == {}
    assert len(_spans(record, "step_lower")) == 1
    assert len(_spans(record, "step_compile")) == 1
    assert "mlp/gate_up/base" not in _recomputed_matmuls(built._compiled)


def test_full_device_keeps_nothing(monkeypatch, devices, record):
    built = _aot_build(monkeypatch, devices, limit=1 << 16)
    assert built.info["remat_keep"] == []
    assert built.info["remat_budget_bytes"] < 0
    assert built.info["remat_keep_fallback"] is False


class _OutOfHbm:
    def lower(self, *a, **k):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
            "Ran out of memory in memory space hbm.")


def _no_room(monkeypatch, fault):
    """A stub of the step's sizer under which the estimate picks every
    name and the compiler (its out-of-HBM error) or the compiled step's
    own peak says no."""
    def stub(real):
        if fault == "compile_out_of_hbm":
            return dataclasses.replace(real,
                                       with_keep=lambda k: _OutOfHbm())
        monkeypatch.setattr(cache, "_past_peak",
                            lambda compiled, limit: "its peak passes")
        return real
    return stub


@pytest.mark.parametrize("fault", ["compile_out_of_hbm", "peak_in_reserve"])
def test_no_room_builds_again_with_nothing_kept(monkeypatch, devices,
                                                record, fault, caplog):
    with caplog.at_level("WARNING"):
        built = _aot_build(monkeypatch, devices, limit=1 << 30,
                           stub=_no_room(monkeypatch, fault))
    (span,) = _spans(record, "step_build")
    assert span["remat_keep_fallback"] is True
    assert span["remat_keep"] == [] and span["remat_keep_bytes"] == 0
    assert built.info["remat_keep_fallback"] is True
    assert "mlp/gate_up/base" in _recomputed_matmuls(built._compiled)
    warned = [r.getMessage() for r in caplog.records
              if "nothing kept" in r.getMessage()]
    assert len(warned) == 1 and "mlp/gate_up" in warned[0]
    assert " GB a device" in warned[0]


def test_a_restart_does_not_try_again_what_did_not_fit(
        monkeypatch, devices, record, tmp_path):
    """The sidecar of a build that fell back holds the step with nothing
    kept under the key of the fallback: the next process neither lowers
    nor compiles, and says what it runs."""
    sidecar = str(tmp_path / "aot_train_step.bin")
    first = _aot_build(monkeypatch, devices, limit=1 << 30, sidecar=sidecar,
                       stub=_no_room(monkeypatch, "compile_out_of_hbm"))
    assert first.info["source"] == "compiled"
    if "serialize_s" not in first.info:
        pytest.skip("this backend's executable does not round-trip")
    record.clear()
    again = _aot_build(monkeypatch, devices, limit=1 << 30, sidecar=sidecar,
                       stub=_no_room(monkeypatch, "compile_out_of_hbm"))
    assert again.info["source"] == "deserialized"
    assert again.info["remat_keep_fallback"] is True
    assert again.info["remat_keep"] == []
    assert not _spans(record, "step_lower")
    # a sidecar from another limit (so another keep set) is stale
    record.clear()
    other = _aot_build(monkeypatch, devices, limit=None, sidecar=sidecar)
    assert other.info["source"] == "compiled"
    assert other.info["remat_keep_fallback"] is False


def test_other_compile_errors_are_not_swallowed(monkeypatch, devices,
                                                record):
    class Broken:
        def lower(self, *a, **k):
            raise jax.errors.JaxRuntimeError("INTERNAL: something else")

    with pytest.raises(jax.errors.JaxRuntimeError, match="something else"):
        _aot_build(monkeypatch, devices, limit=1 << 30,
                   stub=lambda real: dataclasses.replace(
                       real, with_keep=lambda k: Broken()))


def test_which_steps_carry_a_sizer(devices):
    from gke_ray_train_tpu.train import make_optimizer, make_train_step
    opt = make_optimizer(1e-2)
    cfg = tiny(remat=True)
    assert isinstance(make_train_step(cfg, opt).remat, StepRemat)
    assert isinstance(make_train_step(cfg, opt, remat_keep=()).remat,
                      StepRemat)
    assert not hasattr(
        make_train_step(cfg, opt, remat_keep=("attn/out",)), "remat")
    for other in (tiny(remat=False), tiny(remat=True, remat_policy="dots")):
        assert not hasattr(make_train_step(other, opt), "remat")
    assert RematChoice() == RematChoice((), 0, None, None, None)
    assert RematChoice(("attn/out",), 7, 9, 20, 4).attrs(fallback=True) == {
        "remat_keep": [], "remat_keep_bytes": 0, "remat_budget_bytes": 9,
        "remat_args_bytes": 4, "remat_keep_fallback": True,
        "remat_estimate_bytes": 20 - step_remat.RESERVE_BYTES - 9}


def test_the_compile_surface_sizes_a_train_step(monkeypatch, devices,
                                                record):
    """``compile_step_with_plan`` with abstract arguments is where the
    entry points and the benchmark build their step: it goes through the
    step's sizer; the AOT cache itself knows nothing of it."""
    from gke_ray_train_tpu.plan import ExecutionPlan, compile_step_with_plan
    from gke_ray_train_tpu.train import make_train_step
    cfg, opt, state, batch, step_kw = _setup("qlora_xla", devices)
    monkeypatch.setattr(step_remat, "device_bytes_limit",
                        lambda mesh: 1 << 30)
    plan = ExecutionPlan.from_kwargs(grad_accum=2, compile_cache=False)
    step_kw.pop("grad_accum")
    fn = make_train_step(cfg, opt, plan=plan, **step_kw)
    built = compile_step_with_plan(plan, None, fn, state, batch)
    assert built.info["remat_keep"] == ["mlp/gate_up", "attn/qkv",
                                        "attn/out"]
    record.clear()
    plain = cache.build_or_load_step(fn, state, batch)
    assert "remat_keep" not in plain.info
    assert "mlp/gate_up/base" in _recomputed_matmuls(plain._compiled)


@pytest.mark.parametrize("preset, seq, expect", [
    # the routed cell: window 128 at 8192 walks an eighth of its grid at
    # blocks of 512, the full layers the causal triangle at the defaults
    ("k-exaone-236b", 8192, {
        "window": {"block_q": 512, "block_kv": 512, "fwd": [31, 256],
                   "dq": [31, 256], "dkv": [31, 256]},
        "full": {"block_q": 256, "block_kv": 1024, "fwd": [144, 256],
                 "dq": [144, 256], "dkv": [144, 256]}}),
    # the dense cell: window 4096 over 1024 is the whole grid
    ("mistral-7b", 1024, {
        "window": {"block_q": 256, "block_kv": 1024, "fwd": [4, 4],
                   "dq": [4, 4], "dkv": [4, 4]}}),
    # grouped heads of 256 (with a softcap): not what PR 30 swept, so
    # its full causal rows keep the defaults
    ("gemma2-9b", 8192, {
        "window": {"block_q": 512, "block_kv": 1024, "fwd": [60, 128],
                   "dq": [60, 128], "dkv": [60, 128]},
        "full": {"block_q": 256, "block_kv": 1024, "fwd": [144, 256],
                 "dq": [144, 256], "dkv": [144, 256]}}),
    # ungrouped heads of 256: full causal rows take a query block of 512
    # (window_blocks, PR 30's sweep), 72 of 128 steps
    ("glm-4.7-flash", 8192, {
        "latent": {"block_q": 512, "block_kv": 1024, "fwd": [72, 128],
                   "dq": [72, 128], "dkv": [72, 128]}}),
], ids=["routed_8k", "dense_1k", "gemma2_8k", "latent_8k"])
def test_flash_grid_attribute_of_the_presets(preset, seq, expect):
    """The step_build span's flash_grid: grid steps a call visits over
    the rectangular grid's, a head-row here (one row, one head)."""
    from gke_ray_train_tpu.models.config import PRESETS
    from gke_ray_train_tpu.models.transformer import flash_grids
    cfg = PRESETS[preset]()
    # one kv head of the preset's own size with the query heads that
    # share it: the blocks follow the head's size and the grouping
    group = cfg.n_heads // cfg.n_kv_heads
    cfg = dataclasses.replace(cfg, attn_impl="flash", n_heads=group,
                              n_kv_heads=1, head_dim=cfg.resolved_head_dim)
    expect = {kind: {k: v if isinstance(v, int) else [group * c for c in v]
                     for k, v in grid.items()}
              for kind, grid in expect.items()}
    assert flash_grids(cfg, None, 1, seq) == expect
    assert flash_grids(dataclasses.replace(cfg, attn_impl="xla"),
                       None, 1, seq) == {}
    assert flash_grids(dataclasses.replace(cfg, attn_impl="ring"),
                       None, 1, seq) == {}


# ---------------------------------------------------------------------------
# the chooser's estimate beside XLA's own peak (ISSUE 34)
# ---------------------------------------------------------------------------

def _charged(names, sizes):
    return sum(math.ceil(sizes[n] * remat.KEPT_PEAK_SHARE) for n in names)


def test_estimate_is_the_limit_less_the_reserve_and_the_unspent_budget(
        monkeypatch, devices, record):
    limit = 1 << 30
    built = _aot_build(monkeypatch, devices, limit=limit)
    (span,) = _spans(record, "step_build")
    cfg, _, _, batch, step_kw = _setup("qlora_xla", devices)
    rows, seq = batch["inputs"].shape
    sizes = dict(keep_candidates(cfg, rows // step_kw["grad_accum"], seq,
                                 model=1, flash=False))
    charged = _charged(span["remat_keep"], sizes)
    assert 0 < charged < span["remat_keep_bytes"]
    assert span["remat_estimate_bytes"] == (
        limit - step_remat.RESERVE_BYTES
        - (span["remat_budget_bytes"] - charged))
    assert built.info["remat_estimate_bytes"] == \
        span["remat_estimate_bytes"]
    # which is the arguments, the working set and what is kept, charged
    working_set = (limit - step_remat.RESERVE_BYTES
                   - span["remat_args_bytes"] - span["remat_budget_bytes"])
    assert working_set > 0
    assert span["remat_estimate_bytes"] == (
        span["remat_args_bytes"] + working_set + charged)
    # XLA's own beside it, against the limit the chooser was given
    assert span["xla_memory"] == {} or \
        span["xla_memory"]["limit"] == limit


@pytest.mark.parametrize("limit,expected", [
    (None, None),                  # no limit reported: no estimate
    (1 << 16, "args + working set"),    # no room: nothing charged
])
def test_estimate_with_nothing_kept(monkeypatch, devices, record, limit,
                                    expected):
    built = _aot_build(monkeypatch, devices, limit=limit)
    (span,) = _spans(record, "step_build")
    assert span["remat_keep"] == []
    if expected is None:
        assert span["remat_estimate_bytes"] is None
        assert built.info["remat_estimate_bytes"] is None
    else:
        assert span["remat_estimate_bytes"] == (
            limit - step_remat.RESERVE_BYTES - span["remat_budget_bytes"])
        assert span["remat_estimate_bytes"] > span["remat_args_bytes"]


def test_the_fallback_s_estimate_charges_nothing(monkeypatch, devices,
                                                 record):
    limit = 1 << 30
    _aot_build(monkeypatch, devices, limit=limit,
               stub=_no_room(monkeypatch, "compile_out_of_hbm"))
    (span,) = _spans(record, "step_build")
    assert span["remat_keep_fallback"] is True
    assert span["remat_estimate_bytes"] == (
        limit - step_remat.RESERVE_BYTES - span["remat_budget_bytes"])


def test_remat_choice_arithmetic_by_hand():
    choice = RematChoice(
        keep=("a", "b"), keep_bytes=1_000, budget_bytes=2_000,
        limit_bytes=10_000 + step_remat.RESERVE_BYTES, args_bytes=3_000,
        charged_bytes=920)
    assert choice.estimate_bytes() == 10_000 - (2_000 - 920)
    assert choice.estimate_bytes(fallback=True) == 10_000 - 2_000
    assert choice.attrs()["remat_estimate_bytes"] == 8_920
    assert choice.attrs(fallback=True)["remat_estimate_bytes"] == 8_000
    assert RematChoice().estimate_bytes() is None
    assert RematChoice().attrs()["remat_estimate_bytes"] is None
