"""Weight quantization (ops/quant.py) — NF4/int8 QLoRA parity (D5)."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gke_ray_train_tpu.ops.quant import (
    NF4_CODEBOOK, QTensor, dequantize, is_qtensor, quant_specs,
    quantize_params, quantize_tensor)


@pytest.mark.parametrize("kind,tol", [("nf4", 0.15), ("int8", 0.012)])
def test_round_trip_error_bounds(kind, tol):
    w = jax.random.normal(jax.random.key(0), (2, 128, 64)) * 0.02
    qt = quantize_tensor(w, kind)
    back = dequantize(qt, jnp.float32)
    assert back.shape == w.shape
    # relative error vs per-group absmax
    err = np.abs(np.asarray(back - w))
    scale = np.abs(np.asarray(w)).max()
    assert err.max() / scale < tol, f"{kind}: {err.max() / scale}"


def test_nf4_storage_is_4bit_codes():
    w = jax.random.normal(jax.random.key(1), (64, 32))
    qt = quantize_tensor(w, "nf4")
    assert qt.codes.dtype == jnp.uint4      # the one storage
    assert qt.shape == w.shape
    codes = np.asarray(qt.codes.astype(jnp.int32))
    assert codes.min() >= 0 and codes.max() <= 15


def test_nf4_codes_cost_half_a_byte_as_arguments():
    """What the device holds of the frozen base: XLA bills an executable
    two codes a byte (``itemsize`` and ``nbytes`` say 1 a code)."""
    w = jax.random.normal(jax.random.key(3), (2, 128, 64))
    qt = quantize_tensor(w, "nf4")

    def arguments(x):
        return jax.jit(lambda c: c.astype(jnp.int32).sum()).lower(
            x).compile().memory_analysis().argument_size_in_bytes

    assert arguments(qt.codes) == w.size // 2
    assert arguments(qt.codes.astype(jnp.int8)) == w.size


def _stacked_as_the_benchmark_draws_it(w, kind, group):
    """``benchmark/drivers/common.py::params_maker``: a layer at a time
    under ``lax.map``, the leaf made from its four parts."""
    def one(r):
        qt = quantize_tensor(w[r][None], kind, group)
        return qt.codes[0], qt.scales[0]
    codes, scales = jax.jit(lambda: jax.lax.map(
        one, jnp.arange(w.shape[0], dtype=jnp.int32)))()
    return QTensor(codes, scales, kind,
                   codes.shape[-2] // scales.shape[-2])


@pytest.mark.parametrize("shape,group,make", [
    ((2, 128, 64), 64, quantize_tensor),
    ((3, 96, 8), 64, quantize_tensor),              # falls back to 48
    ((2, 4, 128, 32), 64, quantize_tensor),         # [layers, experts, D, F]
    ((3, 128, 64), 64, _stacked_as_the_benchmark_draws_it),
], ids=["group_64", "odd_group", "bank_axis", "stacked_lax_map"])
def test_nf4_dequantize_gives_the_bits_of_a_byte_a_code(shape, group, make):
    """Two codes a byte are the codes a byte each held before: the same
    values out of ``dequantize``, alone and through a jitted matmul."""
    w = jax.random.normal(jax.random.key(4), shape) * 0.02
    qt = make(w, "nf4", group)
    assert qt.codes.dtype == jnp.uint4 and qt.shape == shape
    direct = quantize_tensor(w, "nf4", group)
    assert qt.group == direct.group
    byte_a_code = QTensor(direct.codes.astype(jnp.int8), direct.scales,
                          "nf4", direct.group)
    for dtype in (jnp.float32, jnp.bfloat16):
        np.testing.assert_array_equal(
            np.asarray(dequantize(qt, dtype).astype(jnp.float32)),
            np.asarray(dequantize(byte_a_code, dtype).astype(jnp.float32)))
    x = jax.random.normal(jax.random.key(5), (5, shape[-2]))
    prod = jax.jit(lambda q: jnp.einsum(
        "td,...df->...tf", x, dequantize(q, jnp.float32)))
    np.testing.assert_array_equal(np.asarray(prod(qt)),
                                  np.asarray(prod(byte_a_code)))


def _chain_lookup(codes):
    """The decode up to PR 30, kept as the oracle."""
    c = codes.astype(jnp.int32)
    out = jnp.full(c.shape, NF4_CODEBOOK[0], jnp.float32)
    for i in range(1, 16):
        out = jnp.where(c == i, NF4_CODEBOOK[i], out)
    return out


@pytest.mark.parametrize("scale", [0.0625, 0.0437219],
                         ids=["scale_pow2", "scale_odd"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("code", range(16))
def test_nf4_decode_is_the_codebook_entry_times_the_scale(code, dtype,
                                                          scale):
    """``NF4_CODEBOOK[code] * scale`` as a float32 product, cast: bit
    for bit, from the jitted select tree and from the eager CPU table."""
    qt = QTensor(jnp.full((8, 2), code, jnp.uint4),
                 jnp.full((2, 2), scale, jnp.float32), "nf4", 4)
    want = (np.float32(NF4_CODEBOOK[code]) * np.float32(scale)).astype(
        jnp.dtype(dtype))
    for got in (jax.jit(lambda q: dequantize(q, dtype))(qt),
                dequantize(qt, dtype)):
        assert got.dtype == dtype and got.shape == (8, 2)
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint8),
            np.broadcast_to(want, (8, 2)).copy().view(np.uint8))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_nf4_dequantize_equals_the_chain_it_replaced(dtype, monkeypatch):
    """A whole ``dequantize``, jitted alone and as the operand of a
    product, against the fifteen-step chain of PR 30 and before."""
    from gke_ray_train_tpu.ops import quant
    w = jax.random.normal(jax.random.key(31), (3, 128, 256)) * 0.02
    qt = quantize_tensor(w, "nf4")
    x = jax.random.normal(jax.random.key(32), (3, 16, 128), dtype)
    def run():  # a new function a call, so that each traces its lookup
        return jax.jit(lambda q: (
            dequantize(q, dtype),
            jnp.einsum("emd,edf->emf", x, dequantize(q, dtype))))(qt)

    got = run()
    monkeypatch.setattr(quant, "_nf4_lookup", _chain_lookup)
    want = run()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def test_nf4_lookup_is_the_select_tree():
    """15 selects on four masks: no compare with a code's value (the
    chain of PR 30 and before took fifteen) and no gather."""
    from gke_ray_train_tpu.ops import quant

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    names = list(primitives(jax.make_jaxpr(quant._nf4_lookup)(
        jnp.zeros((64,), jnp.uint4)).jaxpr))
    assert names.count("select_n") == 15
    assert sum(names.count(n) for n in ("and", "ne", "ge")) == 7
    assert "eq" not in names and "gather" not in names


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


@pytest.mark.parametrize("mode", ["alone", "product"])
@pytest.mark.parametrize("kind,code_dtype", [("nf4", jnp.uint4),
                                             ("int8", jnp.int8)])
def test_v5e_decode_is_one_fusion_and_no_broadcast_in_hbm(
        kind, code_dtype, mode, v5e):
    """What PR 31's gain rests on, at the dense gate/up shape: compiled
    for the v5e, a decode is ONE fusion, and the scales' broadcast is
    not an operation of its own that goes through HBM at 4 bytes a
    weight (the program's only temporary is the decoded weight a
    product reads: 2 bytes a weight). Without ``dequantize``'s barrier
    XLA sinks the group reshape onto the broadcast and both fail."""
    from jax.sharding import SingleDeviceSharding
    from gke_ray_train_tpu.ops.quant import DEFAULT_GROUP
    from gke_ray_train_tpu.plan import XLA_TPU_OPTIONS
    rows, D, F = 2048, 4096, 14336
    sharding = SingleDeviceSharding(v5e)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    qt = QTensor(spec((D, F), code_dtype),
                 spec((D // DEFAULT_GROUP, F), jnp.float32), kind,
                 DEFAULT_GROUP)
    fn = {"alone": lambda x, q: dequantize(q, jnp.bfloat16),
          "product": lambda x, q: x @ dequantize(q, jnp.bfloat16)}[mode]
    built = jax.jit(fn, compiler_options=XLA_TPU_OPTIONS).lower(
        spec((rows, D), jnp.bfloat16), qt).compile()
    hlo = built.as_text()
    entry = re.findall(r"= \S+ ([a-z][\w-]*)\(", hlo[hlo.index("ENTRY"):])
    assert "broadcast" not in entry, entry
    assert entry.count("fusion") == {"alone": 1, "product": 2}[mode], entry
    assert built.memory_analysis().temp_size_in_bytes <= 2.5 * D * F


def test_exact_for_codebook_values():
    """Weights that sit exactly on scaled codebook points reconstruct
    exactly (scale = absmax of the group)."""
    from gke_ray_train_tpu.ops.quant import NF4_CODEBOOK
    scale = 0.5
    w = jnp.asarray(NF4_CODEBOOK * scale)[None, :, None]  # [1, 16, 1]
    qt = quantize_tensor(jnp.broadcast_to(w, (1, 16, 4)), "nf4", group=16)
    back = dequantize(qt, jnp.float32)
    np.testing.assert_allclose(back[0, :, 0], NF4_CODEBOOK * scale,
                               atol=1e-6)


def test_odd_group_fallback():
    w = jax.random.normal(jax.random.key(2), (3, 96, 8))  # 96 % 64 != 0
    qt = quantize_tensor(w, "nf4")
    assert qt.group == 48  # largest divisor of 96 <= 64
    assert dequantize(qt).shape == w.shape


def test_quantize_params_targets_only_projections():
    from gke_ray_train_tpu.models import init_params, tiny

    cfg = tiny(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=128)
    params = init_params(cfg, jax.random.key(0))
    qp = quantize_params(params, "nf4")
    blk = qp["blocks"][0]
    for t in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert is_qtensor(blk[t]), t
    assert not is_qtensor(blk["attn_norm"])
    assert not is_qtensor(qp["embed"])


def test_forward_with_quantized_base_close_to_fp():
    from gke_ray_train_tpu.models import forward, init_params, tiny

    cfg = tiny(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=128, dtype="float32",
               param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)
    ref = forward(params, tokens, cfg)
    out = forward(quantize_params(params, "int8"), tokens, cfg)
    # int8 per-group: logits drift but ordering should survive
    agree = (np.argmax(np.asarray(out), -1)
             == np.argmax(np.asarray(ref), -1)).mean()
    assert agree > 0.95, agree


def test_qlora_train_step_loss_decreases():
    """Full QLoRA slice: NF4 frozen base + trainable LoRA on a sharded
    mesh; only adapters update, loss decreases."""
    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    from gke_ray_train_tpu.parallel.sharding import tree_shardings
    from gke_ray_train_tpu.models.transformer import param_specs
    from gke_ray_train_tpu.train import (
        LoraConfig, make_optimizer, make_train_state, make_train_step,
        warmup_cosine_schedule)
    from gke_ray_train_tpu.train.step import TrainState, batch_shardings

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2, context=1))
    cfg = tiny(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=128, dtype="float32",
               param_dtype="float32")
    lora_cfg = LoraConfig(r=4, alpha=8.0)
    sch = warmup_cosine_schedule(5e-3, 20)
    opt = make_optimizer(sch)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh,
                             lora_cfg=lora_cfg)
    qparams = quantize_params(state.params, "nf4")
    state = TrainState(params=qparams, lora=state.lora,
                       opt_state=state.opt_state, step=state.step)
    # donate_batch=False: the loop below re-feeds one placed batch
    step = make_train_step(cfg, opt, mesh=mesh, lora_cfg=lora_cfg,
                           schedule=sch, donate_batch=False)
    B, S = 4, 32
    batch = {
        "inputs": jax.random.randint(jax.random.key(1), (B, S), 0, 64),
        "targets": jax.random.randint(jax.random.key(2), (B, S), 0, 64),
        "weights": jnp.ones((B, S), jnp.float32),
    }
    batch = jax.device_put(batch, batch_shardings(mesh))
    losses = []
    for _ in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    # frozen base unchanged (still the same quantized codes)
    assert is_qtensor(state.params["blocks"][0]["wq"])


def test_merge_lora_with_quantized_base():
    from gke_ray_train_tpu.models import init_params, tiny
    from gke_ray_train_tpu.train import LoraConfig
    from gke_ray_train_tpu.train.lora import init_lora, merge_lora

    cfg = tiny(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=128)
    params = init_params(cfg, jax.random.key(0))
    lora_cfg = LoraConfig(r=4, alpha=8.0)
    lora = init_lora(cfg, lora_cfg, jax.random.key(1))
    # make b nonzero so the merge moves weights
    lora = jax.tree.map(lambda x: x + 0.01, lora)

    merged_fp = merge_lora(params, lora, lora_cfg)
    merged_q = merge_lora(quantize_params(params, "int8"), lora, lora_cfg)
    wq_fp = np.asarray(merged_fp["blocks"][0]["wq"], dtype=np.float32)
    wq_q = np.asarray(merged_q["blocks"][0]["wq"], dtype=np.float32)
    assert not is_qtensor(merged_q["blocks"][0]["wq"])
    np.testing.assert_allclose(wq_q, wq_fp, atol=2e-3)

    # on_host merge (the single-host big-model export path): identical
    # values, every leaf committed to a CPU device
    merged_h = merge_lora(quantize_params(params, "int8"), lora, lora_cfg,
                          on_host=True)
    np.testing.assert_allclose(
        np.asarray(merged_h["blocks"][0]["wq"], dtype=np.float32), wq_q,
        atol=1e-6)
    leaf = merged_h["blocks"][0]["wq"]
    assert list(leaf.devices())[0].platform == "cpu"


def test_quant_specs_and_sharding():
    from gke_ray_train_tpu.models import init_params, tiny
    from gke_ray_train_tpu.models.transformer import param_specs
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    from gke_ray_train_tpu.parallel.sharding import tree_shardings

    mesh = build_mesh(MeshConfig(data=1, fsdp=4, model=2, context=1))
    cfg = tiny(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=128)
    params = quantize_params(init_params(cfg, jax.random.key(0)), "nf4")
    specs = quant_specs(param_specs(cfg), params, mesh)
    sharded = jax.device_put(params, tree_shardings(mesh, specs))
    wq = sharded["blocks"][0]["wq"]
    assert is_qtensor(wq)
    # codes sharded like the fp weight would be
    assert wq.codes.sharding.spec == param_specs(cfg)["blocks"][0]["wq"]


def test_merge_lora_partial_targets_dequantizes_rest():
    """q/v-only LoRA over a fully quantized base: merge must return plain
    arrays for ALL weights (the HF export cannot take QTensors)."""
    from gke_ray_train_tpu.models import init_params, tiny
    from gke_ray_train_tpu.train import LoraConfig
    from gke_ray_train_tpu.train.lora import init_lora, merge_lora

    cfg = tiny(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=128)
    params = quantize_params(init_params(cfg, jax.random.key(0)), "nf4")
    lora_cfg = LoraConfig(r=4, alpha=8.0, targets=("wq", "wv"))
    lora = init_lora(cfg, lora_cfg, jax.random.key(1))
    merged = merge_lora(params, lora, lora_cfg)
    for blk in merged["blocks"]:
        for t in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            assert not is_qtensor(blk[t]), t
