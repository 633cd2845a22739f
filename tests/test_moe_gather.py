"""The routed layer's gather-and-sum (``ops/moe.py::gather_sum``, ISSUE
35): the kernel ``moe_gather_sum`` (interpreted here) and the
``jax.numpy`` form against the two expressions it replaced, the layer's
value and gradients with the kernel forced on against the ``jax.numpy``
form, and the plan at the three routed cells' shapes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gke_ray_train_tpu.models.config import PRESETS, tiny
from gke_ray_train_tpu.ops import moe

PALLAS = moe.GatherPlan("pallas", 8)
XLA = moe.GatherPlan("xla", 0)


def combine_before(out, w, row):
    """``_weighted_rows`` before PR 35 (its buffer's dead rows zeroed)."""
    return jnp.einsum("tkd,tk->td", out[row], w.astype(out.dtype),
                      preferred_element_type=jnp.float32).astype(out.dtype)


def dispatch_bwd_before(g, row, held):
    """``_dispatch_bwd`` before PR 35."""
    return jnp.sum(jnp.where(held[..., None], g[row], 0), axis=1,
                   dtype=jnp.float32).astype(g.dtype)


def buffer(tokens, picks, width, dtype, seed):
    """(buf, row, w, held) as ``routed_experts`` has them: a token's held
    picks sorted by expert to the front of the buffer, the rows past the
    live ones holding what a kernel left there (NaN among it), some held
    weights 0."""
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.random((tokens, 4 * picks)), axis=1)[:, :picks]
    held = idx < picks
    e = np.where(held, idx, picks).reshape(-1)
    order = np.argsort(e, kind="stable")
    row = np.empty(tokens * picks, np.int32)
    row[order] = np.arange(tokens * picks, dtype=np.int32)
    buf = rng.standard_normal((tokens * picks, width)).astype(np.float32)
    live = int(held.sum())
    buf[live:] = np.nan
    w = rng.random((tokens, picks)).astype(np.float32) * held
    w[0, :] = 0.0
    return (jnp.asarray(buf, dtype), jnp.asarray(row.reshape(tokens, picks)),
            jnp.asarray(w), jnp.asarray(held), live)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("use", ["combine", "dispatch_bwd"])
@pytest.mark.parametrize("picks", [4, 8, 10])
def test_gather_sum_is_the_expressions_it_replaced(picks, use, dtype):
    """Both forms, the weights of each use, to the last bit but for the
    order of a K-term sum: one unit of the last place at most, in few
    elements. Rows past the live ones (NaN here) add nothing."""
    dtype = jnp.dtype(dtype)
    width = 128 * (8 if dtype.itemsize == 4 else 16)   # whole tiles
    buf, row, w, held, live = buffer(24, picks, width, dtype, seed=picks)
    if use == "combine":
        w = w.astype(dtype)      # rounded as _weighted_rows rounds them
        zeroed = buf.at[live:].set(0)
        want = combine_before(zeroed, w, row)
    else:
        w = held
        want = dispatch_bwd_before(buf, row, held)
    # bf16: one rounding hides the order but for a rare tie; float32
    # keeps the order's last bits
    packed = dtype.itemsize == 2
    ulp = float(jnp.finfo(dtype).eps) * float(jnp.max(jnp.abs(
        want.astype(jnp.float32)))) * (1 if packed else picks)
    for plan in (PALLAS, XLA):
        got = moe.gather_sum(buf, row, w, plan=plan)
        assert got.dtype == dtype and got.shape == (24, width)
        gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
        assert np.isfinite(np.asarray(got, np.float32)).all(), plan
        assert gap.max() <= ulp, plan
        assert not packed or (gap > 0).mean() < 0.02, plan


def routed_setup(router):
    k = jax.random.split(jax.random.key(35), 6)
    D, E, F = 1024, 16, 16
    cfg = tiny(d_model=D, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64,
               n_experts=E, expert_top_k=4, expert_d_ff=F, router=router,
               router_bias=router == "sigmoid", router_scale=2.5,
               experts_held=(4, 12))
    lp = {"router": jax.random.normal(k[0], (D, E)) * 0.05,
          "w_gate": jax.random.normal(k[2], (8, D, F)) * 0.05,
          "w_up": jax.random.normal(k[3], (8, D, F)) * 0.05,
          "w_down": jax.random.normal(k[4], (8, F, D)) * 0.05}
    if router == "sigmoid":
        lp["router_bias"] = jax.random.normal(k[1], (E,)) * 0.1
    x = jax.random.normal(k[5], (2, 16, D))
    return cfg, lp, x


@pytest.mark.parametrize("router", ["sigmoid", "topk_softmax"])
def test_routed_layer_with_the_kernel_forced_on(monkeypatch, router):
    """``routed_experts``' value and its gradients with respect to x and
    the bank: the kernel (tiles of 8 tokens, four grid steps) against
    the ``jax.numpy`` form, through both uses."""
    cfg, lp, x = routed_setup(router)

    def loss(x, lp):
        y, counters = moe.routed_experts(x, lp, cfg, jnp.float32)
        return jnp.sum(jnp.sin(y)), (y, counters)

    def run(min_row_bytes):
        monkeypatch.setattr(moe, "GATHER_MIN_ROW_BYTES", min_row_bytes)
        monkeypatch.setattr(moe, "GATHER_TILE", 8)
        return jax.grad(loss, argnums=(0, 1), has_aux=True)(x, lp)
    assert moe.gather_plan(32 * 4, 1024, 4, jnp.float32) == XLA
    (gx, glp), (y, counters) = run(0)
    (gx_x, glp_x), (y_x, counters_x) = run(1 << 30)
    assert counters == counters_x and counters["moe_pairs"] > 0
    # float32 sums in another order: a few units of the last place
    np.testing.assert_allclose(y, y_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gx, gx_x, rtol=1e-5, atol=1e-5)
    for name in ("w_gate", "w_up", "w_down"):
        np.testing.assert_allclose(glp[name], glp_x[name], rtol=1e-5,
                                   atol=1e-5, err_msg=name)


@pytest.mark.parametrize("rows,width,picks,want", [
    (81920, 4096, 10, PALLAS._replace(token_tile=32)),   # hybrid
    (65536, 6144, 8, PALLAS._replace(token_tile=32)),    # routed
    (32768, 2048, 4, XLA),                                # latent: 4 KB
    (81920, 4000, 10, XLA),                               # not whole tiles
], ids=["hybrid", "routed", "latent", "ragged"])
def test_plan_at_the_routed_cells_shapes(rows, width, picks, want):
    assert moe.gather_plan(rows, width, picks, jnp.bfloat16) == want


@pytest.mark.parametrize("preset,held,want", [
    ("granite-4.0-h-small", (0, 18), ("pallas", 32, 81920, 8192, 10)),
    ("k-exaone-236b", (0, 16), ("pallas", 32, 65536, 12288, 8)),
    ("glm-4.7-flash", (0, 16), ("xla", 0, 32768, 4096, 4)),
    ("mistral-7b", None, None),
])
def test_moe_gather_attribute_of_the_presets(preset, held, want):
    """The ``step_build`` span's ``moe_gather`` for a micro-batch of one
    packed row of 8192, as the three routed cells run it; ``{}`` for a
    model without a routed layer."""
    cfg = PRESETS[preset]()
    if held is not None:
        cfg = dataclasses.replace(cfg, experts_held=held)
    got = moe.gather_geometry(cfg, 8192)
    if want is None:
        assert got == {}
        return
    assert got == dict(zip(("impl", "token_tile", "rows", "row_bytes",
                            "picks"), want))

