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


# -- the decode inside the product: ops/quant.py::nf4_matmul ----------------

def _small_plan():
    """The smallest tiles that qualify: two grid steps on every axis."""
    from gke_ray_train_tpu.ops.quant import Nf4Plan
    return Nf4Plan("pallas", 16, 128, 512, 128, 512)


def _operands(rows, depth, cols, dtype, seed=0):
    kx, kw, kd = jax.random.split(jax.random.key(seed), 3)
    w = jax.random.normal(kw, (depth, cols), jnp.float32) * 0.02
    return (jax.random.normal(kx, (rows, depth), jnp.float32).astype(dtype),
            quantize_tensor(w, "nf4"),
            jax.random.normal(kd, (rows, cols), jnp.float32).astype(dtype))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_nf4_matmul_is_dequantize_then_the_product(dtype):
    """The kernel pair (interpreted) against ``dequantize`` + einsum,
    forward and dx: the same decoded weights and float32 sums, so the
    results differ only by the order of a sum."""
    from gke_ray_train_tpu.ops.quant import nf4_matmul
    x, qt, dy = _operands(32, 1024, 256, dtype)

    def ours(x):
        return nf4_matmul(x, qt, plan=_small_plan())

    def theirs(x):
        return jnp.einsum("md,dn->mn", x, dequantize(qt, dtype),
                          preferred_element_type=jnp.float32).astype(dtype)

    (y, vjp), (y_ref, vjp_ref) = jax.vjp(ours, x), jax.vjp(theirs, x)
    (dx,), (dx_ref,) = vjp(dy), vjp_ref(dy)
    assert y.dtype == dx.dtype == dtype and dx.shape == x.shape
    # bf16: a unit of the last place at the largest magnitude; float32:
    # a few, a sum of 1024 products in another order
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -18
    for got, want in ((y, y_ref), (dx, dx_ref)):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.max(np.abs(got - want)) <= ulp * np.max(np.abs(want))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_nf4_decoded_tile_is_dequantize_bit_for_bit(dtype):
    """What the kernel hands the MXU is ``dequantize``'s weights to the
    last bit: every code, scales of both signs' groups and a zero
    group, a tile at a time through the kernel's own decode."""
    from jax.experimental import pallas as pl
    from gke_ray_train_tpu.ops.quant import _decode_tile
    depth, cols, tk, tn = 1024, 256, 512, 128
    codes = (jnp.arange(depth * cols, dtype=jnp.int32) * 7 % 16).reshape(
        depth, cols).astype(jnp.uint4)
    scales = jax.random.uniform(jax.random.key(2), (depth // 64, cols),
                                jnp.float32, 1e-3, 0.1)
    qt = QTensor(codes, scales.at[3].set(0.0), "nf4", 64)

    def kernel(c_ref, s_ref, o_ref):
        o_ref[...] = _decode_tile(c_ref[...], s_ref[...], 64, dtype)
    decoded = pl.pallas_call(
        kernel, grid=(depth // tk, cols // tn),
        in_specs=[pl.BlockSpec((tk, tn), lambda i, j: (i, j)),
                  pl.BlockSpec((tk // 64, tn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tk, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((depth, cols), dtype),
        interpret=True)(qt.codes, qt.scales)
    want = jax.jit(lambda q: dequantize(q, dtype))(qt)
    bits = jnp.uint16 if dtype == jnp.bfloat16 else jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(jax.lax.bitcast_convert_type(decoded, bits)),
        np.asarray(jax.lax.bitcast_convert_type(want, bits)))


def test_proj_takes_the_kernel_on_one_device_and_agrees_under_grad():
    """``_proj`` with adapters through ``jax.grad``: on a mesh of one
    device, at rows the plan takes, the base is the kernel pair (both in
    the program, under the ``base`` scope); with no mesh it is the
    decode and the einsum. The loss and the gradients of x and of both
    adapter matrices agree within bf16's rounding."""
    from jax.sharding import Mesh
    from gke_ray_train_tpu.models.transformer import _proj
    from gke_ray_train_tpu.ops.quant import NF4_MIN_ROWS
    rows, depth, cols = NF4_MIN_ROWS, 512, 256
    x, qt, _ = _operands(rows, depth, cols, jnp.bfloat16, seed=3)
    x = x.reshape(2, rows // 2, depth)
    lora = {"a": jax.random.normal(jax.random.key(4), (depth, 8)) * 0.02,
            "b": jax.random.normal(jax.random.key(5), (8, cols)) * 0.02}
    one = Mesh(np.array(jax.devices()[:1]), ("data",))

    def loss(x, lora, mesh):
        y = _proj(x, qt, lora, 2.0, jnp.bfloat16, mesh=mesh)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def grads(mesh):
        return jax.jit(jax.value_and_grad(
            lambda x, lo: loss(x, lo, mesh), argnums=(0, 1)))(x, lora)

    text = str(jax.make_jaxpr(jax.grad(
        lambda x, lo: loss(x, lo, one), argnums=(0, 1)))(x, lora))
    assert "nf4_matmul" in text and "nf4_matmul_dx" in text
    assert "nf4_matmul" not in str(jax.make_jaxpr(jax.grad(
        lambda x, lo: loss(x, lo, None), argnums=(0, 1)))(x, lora))
    (l1, (gx1, gl1)), (l2, (gx2, gl2)) = grads(one), grads(None)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-2)
    for a, b in zip(jax.tree.leaves((gx1, gl1)), jax.tree.leaves((gx2, gl2))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= 2e-2 * np.max(np.abs(b))


@pytest.mark.parametrize("rows,depth,cols,kind,want", [
    (2048, 4096, 14336, "nf4", ("pallas", 2048, 2048, 512, 1024, 1024)),
    (2048, 14336, 4096, "nf4", ("pallas", 2048, 2048, 512, 1024, 1024)),
    (2048, 4096, 4096, "nf4", ("pallas", 2048, 2048, 512, 1024, 1024)),
    (2048, 4096, 1024, "nf4", ("pallas", 2048, 1024, 512, 1024, 1024)),
    (32, 4096, 14336, "nf4", ("pallas", 32, 2048, 512, 1024, 1024)),
    (2048, 2048, 576, "nf4", ("xla", 0, 0, 0, 0, 0)),  # latent kv down
    (2048, 4096, 14336, "int8", ("xla", 0, 0, 0, 0, 0)),
    (16, 4096, 14336, "nf4", ("xla", 0, 0, 0, 0, 0)),   # not measured
    (8192, 4096, 14336, "nf4", ("xla", 0, 0, 0, 0, 0)),  # one row of 8192
    (2048, 96, 8, "nf4", ("xla", 0, 0, 0, 0, 0)),       # a tiny preset
], ids=["dense_gate_up", "dense_down", "dense_q_o", "dense_k_v",
        "decode_32", "cols_576", "int8", "rows_16", "rows_8192", "tiny"])
def test_nf4_matmul_plan_reads_shapes(rows, depth, cols, kind, want):
    from gke_ray_train_tpu.ops.quant import nf4_matmul_plan
    assert tuple(nf4_matmul_plan(rows, depth, cols, kind)) == want


def _abstract_quantized(preset, kind):
    from gke_ray_train_tpu.models import init_params
    from gke_ray_train_tpu.models.config import PRESETS
    cfg = PRESETS[preset]()
    return jax.eval_shape(lambda: quantize_params(
        init_params(cfg, jax.random.key(0)), kind))


def test_nf4_geometry_of_the_dense_cell():
    """The ``step_build`` span's ``nf4_matmul`` for Mistral-7B's frozen
    base at 2048 rows a micro-batch: every projection on the kernel on
    one device, none on a mesh of several, none for int8 leaves."""
    from gke_ray_train_tpu.ops.quant import nf4_geometry
    params = _abstract_quantized("mistral-7b", "nf4")
    got = nf4_geometry(params, 2048, whole=True)
    assert got["rows"] == 2048 and (got["pallas"], got["xla"]) == (224, 0)
    assert {k: v["calls"] for k, v in got["shapes"].items()} == {
        "4096x4096": 64, "4096x1024": 64, "4096x14336": 64,
        "14336x4096": 32}
    assert got["shapes"]["4096x14336"]["tiles"] == [2048, 2048, 512]
    assert got["shapes"]["4096x14336"]["dx_tiles"] == [2048, 1024, 1024]
    shared = nf4_geometry(params, 2048, whole=False)
    assert (shared["pallas"], shared["xla"]) == (0, 224)
    int8 = nf4_geometry(_abstract_quantized("mistral-7b", "int8"), 2048,
                        whole=True)
    assert (int8["pallas"], int8["xla"]) == (0, 224)
    assert nf4_geometry({"w": jnp.zeros((2, 4, 4))}, 2048, True) == {}


def test_v5e_nf4_matmul_pair_compiles_under_base_and_its_phases(v5e):
    """The kernel pair at the dense gate/up shape, compiled for a
    described v5e inside a gradient: Mosaic takes the ``uint4`` codes as
    XLA lays them out (no copy of the codes before either kernel), and
    each custom call's ``op_name`` carries ``base`` and its phase, which
    is what ``proj_share.train`` and ``nf4_matmul_roofline.train`` bill
    by."""
    from jax.sharding import SingleDeviceSharding
    from gke_ray_train_tpu.obs.trace import scope_path
    from gke_ray_train_tpu.ops.quant import nf4_matmul, nf4_matmul_plan
    rows, D, F = 2048, 4096, 14336
    sharding = SingleDeviceSharding(v5e)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    qt = QTensor(spec((D, F), jnp.uint4), spec((D // 64, F), jnp.float32))
    plan = nf4_matmul_plan(rows, D, F, "nf4")

    def loss(x, q):
        with jax.named_scope("base"):
            y = nf4_matmul(x, q, plan=plan, interpret=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("default"):
        hlo = jax.jit(jax.grad(loss)).lower(
            spec((rows, D), jnp.bfloat16), qt).compile().as_text()
    calls = dict(re.findall(
        r"%(nf4_matmul(?:_dx)?)[\w.]* = .*custom-call\(.*"
        r'op_name="([^"]*)"', hlo))
    assert set(calls) == {"nf4_matmul", "nf4_matmul_dx"}, calls
    assert all(scope_path(n).endswith("base") for n in calls.values())
    assert "jvp(" in calls["nf4_matmul"] \
        and "transpose(" not in calls["nf4_matmul"]
    assert "transpose(" in calls["nf4_matmul_dx"]
    assert not re.search(r"= u4\[\S+ (?:copy|fusion)\(", hlo)
