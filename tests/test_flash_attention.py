"""Flash attention (Pallas) vs the XLA oracle — values and grads.

Runs the real kernel under the Pallas interpreter on CPU (conftest pins
JAX_PLATFORMS=cpu); on a TPU the same code path compiles via Mosaic.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gke_ray_train_tpu.ops.attention import (
    dot_product_attention, make_attention_mask)
from gke_ray_train_tpu.ops import flash_attention as fa
from gke_ray_train_tpu.ops.flash_attention import flash_attention


def _rand_qkv(key, B, S, T, H, K, dh, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, dh), dtype)
    k = jax.random.normal(kk, (B, T, K, dh), dtype)
    v = jax.random.normal(kv, (B, T, K, dh), dtype)
    return q, k, v


def _oracle(q, k, v, *, seg=None, causal=True, window=None, softcap=None,
            scale=None):
    B, S = q.shape[:2]
    T = k.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    kpos = jnp.broadcast_to(jnp.arange(T), (B, T))
    mask = make_attention_mask(pos, kpos, seg, seg, causal=causal,
                               sliding_window=window)
    return dot_product_attention(q, k, v, mask, scale=scale,
                                 logit_softcap=softcap)


CASES = {
    "causal": {},
    "noncausal": dict(causal=False),
    "window": dict(window=16),
    "softcap": dict(softcap=30.0),
    "window+softcap": dict(window=24, softcap=20.0),
}


@pytest.mark.parametrize("case", CASES)
def test_forward_matches_oracle(case):
    kw = CASES[case]
    q, k, v = _rand_qkv(jax.random.key(0), B=2, S=128, T=128, H=4, K=2,
                        dh=64)
    ref = _oracle(q, k, v, **kw)
    out = flash_attention(
        q, k, v, causal=kw.get("causal", True),
        sliding_window=kw.get("window"), logit_softcap=kw.get("softcap"),
        block_q=64, block_kv=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_gqa_and_uneven_blocks():
    # H=8 over K=2 (group of 4); S != T; blocks that tile S and T
    q, k, v = _rand_qkv(jax.random.key(1), B=2, S=64, T=128, H=8, K=2,
                        dh=32)
    # non-causal: S != T has no canonical causal alignment here
    ref = _oracle(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_kv=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_packed_segments_and_padding():
    B, S, H, K, dh = 2, 128, 4, 4, 32
    q, k, v = _rand_qkv(jax.random.key(2), B, S, S, H, K, dh)
    # two packed docs + trailing padding (segment 0)
    seg = jnp.concatenate([
        jnp.full((B, 48), 1), jnp.full((B, 48), 2), jnp.full((B, 32), 0),
    ], axis=1).astype(jnp.int32)
    ref = _oracle(q, k, v, seg=seg)
    out = flash_attention(q, k, v, q_segment_ids=seg, kv_segment_ids=seg,
                          block_q=32, block_kv=32)
    # padding rows: oracle softmax degrades to uniform over padding keys,
    # flash returns 0 — both are "don't care" (loss-masked); compare only
    # real tokens
    real = np.asarray(seg != 0)
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(ref)[real],
                               atol=2e-5, rtol=2e-5)


def test_segment_disjoint_blocks_skipped_exactly():
    """Packed rows with block-aligned documents: q blocks of doc 2 vs kv
    blocks of doc 1 are causally LIVE but segment-dead — only the
    segment-disjoint clause of _block_live skips them. Values and grads
    must match the oracle exactly (plus an all-padding tail block)."""
    B, S, H, K, dh = 1, 192, 2, 2, 32
    q, k, v = _rand_qkv(jax.random.key(9), B, S, S, H, K, dh)
    # doc1 = positions 0..63, doc2 = 64..127 (positions restart), padding
    seg = jnp.concatenate([jnp.full((B, 64), 1), jnp.full((B, 64), 2),
                           jnp.zeros((B, 64))], axis=1).astype(jnp.int32)
    pos = jnp.concatenate([jnp.arange(64), jnp.arange(64),
                           jnp.zeros(64)]).astype(jnp.int32)[None]
    cot = jax.random.normal(jax.random.key(10), q.shape)

    def flash(q, k, v):
        return flash_attention(q, k, v, q_positions=pos, kv_positions=pos,
                               q_segment_ids=seg, kv_segment_ids=seg,
                               causal=True, block_q=32, block_kv=32)

    def oracle(q, k, v):
        mask = make_attention_mask(pos, pos, seg, seg, causal=True)
        return dot_product_attention(q, k, v, mask)

    real = np.asarray(seg != 0)[0]
    out, ref = np.asarray(flash(q, k, v)), np.asarray(oracle(q, k, v))
    np.testing.assert_allclose(out[:, real], ref[:, real],
                               atol=2e-5, rtol=2e-5)

    # grads: zero the padding rows' cotangent (oracle's uniform-softmax
    # garbage there is "don't care" and loss-masked in real use)
    mcot = cot * jnp.asarray(real)[None, :, None, None]
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) * mcot),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(oracle(*a) * mcot),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_window_expired_blocks_skipped_exactly():
    """Long sliding-window sequence where whole KV blocks are BOTH
    causally past and window-expired (S=512, window=64, 64-wide blocks:
    e.g. q block [256,320) vs kv block [0,64) is dead) — the block-level
    skip predicate (_block_live) must not change values or grads."""
    q, k, v = _rand_qkv(jax.random.key(7), B=1, S=512, T=512, H=2, K=2,
                        dh=32)
    cot = jax.random.normal(jax.random.key(8), q.shape)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, sliding_window=64,
                              block_q=64, block_kv=64)
        return jnp.sum(out * cot)

    def loss_ref(q, k, v):
        return jnp.sum(_oracle(q, k, v, window=64) * cot)

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True, sliding_window=64,
                                   block_q=64, block_kv=64)),
        np.asarray(_oracle(q, k, v, window=64)), atol=2e-5, rtol=2e-5)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("case", ["causal", "softcap", "window"])
def test_grads_match_oracle(case):
    kw = CASES[case]
    q, k, v = _rand_qkv(jax.random.key(3), B=1, S=64, T=64, H=4, K=2,
                        dh=32)
    seg = jnp.concatenate(
        [jnp.full((1, 40), 1), jnp.full((1, 24), 2)], axis=1
    ).astype(jnp.int32)
    cot = jax.random.normal(jax.random.key(4), q.shape)

    def loss_flash(q, k, v):
        out = flash_attention(
            q, k, v, q_segment_ids=seg, kv_segment_ids=seg,
            causal=kw.get("causal", True), sliding_window=kw.get("window"),
            logit_softcap=kw.get("softcap"), block_q=32, block_kv=32)
        return jnp.sum(out * cot)

    def loss_ref(q, k, v):
        return jnp.sum(_oracle(q, k, v, seg=seg, **kw) * cot)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch [{case}]")


def test_jit_and_dtype_preserved():
    q, k, v = _rand_qkv(jax.random.key(5), B=1, S=64, T=64, H=2, K=2,
                        dh=32, dtype=jnp.bfloat16)
    fn = jax.jit(functools.partial(flash_attention, block_q=32,
                                   block_kv=32))
    out = fn(q, k, v)
    assert out.dtype == jnp.bfloat16
    assert out.shape == q.shape
    ref = _oracle(q, k, v)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=3e-2,
                               rtol=3e-2)


def test_model_forward_with_flash_matches_xla():
    """End-to-end: the transformer with attn_impl='flash' equals 'xla'."""
    import dataclasses

    from gke_ray_train_tpu.models import forward, init_params, tiny

    cfg = tiny(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=128, dtype="float32",
               param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 128)
    seg = jnp.ones((2, 64), jnp.int32)

    ref = forward(params, tokens, cfg, segment_ids=seg)
    cfg_f = dataclasses.replace(cfg, attn_impl="flash")
    out = forward(params, tokens, cfg_f, segment_ids=seg)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_flash_sharded_over_mesh_matches_local():
    """shard_map-wrapped flash on a dp x tp mesh == unsharded flash."""
    import jax
    from gke_ray_train_tpu.ops.dispatch import attention_dispatch
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2, context=1))
    q, k, v = _rand_qkv(jax.random.key(7), B=4, S=128, T=128, H=4, K=2,
                        dh=32)
    ref = _oracle(q, k, v)

    def f(q, k, v):
        return attention_dispatch("flash", q, k, v, mesh=mesh)

    out = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_context_sharded_mesh_rejected():
    import jax
    from gke_ray_train_tpu.ops.dispatch import attention_dispatch
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=1, fsdp=2, model=2, context=2))
    q, k, v = _rand_qkv(jax.random.key(8), B=4, S=128, T=128, H=4, K=2,
                        dh=32)
    with pytest.raises(ValueError, match="ring"):
        attention_dispatch("flash", q, k, v, mesh=mesh)


def test_odd_seq_len_falls_back_to_xla():
    """Model forward with attn_impl='flash' and S not 128-divisible works
    (dense-mask fallback) instead of crashing."""
    import dataclasses

    from gke_ray_train_tpu.models import forward, init_params, tiny

    cfg = tiny(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32", attn_impl="flash")
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 100), 0, 64)
    out = forward(params, tokens, cfg)
    assert out.shape == (1, 100, 64)


# ---------------------------------------------------------------------------
# the band: rows_ordered=True walks only the blocks that causality and
# the window leave alive (ops/flash_attention.py::kernel_bands)
# ---------------------------------------------------------------------------

def _packed_row(lengths, S):
    """positions / segment ids [1, S] as pack_examples deals documents
    of ``lengths`` tokens into one row (positions from 0 in each, a
    padded tail of segment 0)."""
    from gke_ray_train_tpu.data.packing import pack_examples
    docs = [{"input_ids": np.ones(n + 1, np.int32),
             "loss_weights": np.ones(n + 1, np.float32)} for n in lengths]
    rows = list(pack_examples(docs, S))
    assert len(rows) == 1, "the documents must fit one row"
    return (jnp.asarray(rows[0]["positions"])[None],
            jnp.asarray(rows[0]["segment_ids"])[None])


# name: (window, block_q, block_kv, H, K, document lengths or None)
BAND_CASES = {
    "window_inside_kv_block": (48, 128, 256, 2, 2, None),
    "window_straddles_kv_blocks": (200, 128, 128, 2, 2, None),
    "packed_docs_cross_blocks_padded_tail": (64, 128, 128, 2, 2,
                                             (100, 180, 150)),
    "band_clipped_at_zero": (300, 128, 128, 2, 2, None),
    "full_causal_clamped": (None, 128, 256, 2, 2, None),
    "gqa_block_q_wider_than_block_kv": (100, 256, 128, 4, 2, (200, 250)),
}


@functools.lru_cache(maxsize=None)
def _band_case(case):
    """(banded, full grid, oracle) as (out, dq, dk, dv), and the real
    rows, of one case at S = 512."""
    window, bq, bkv, H, K, docs = BAND_CASES[case]
    S = 512
    q, k, v = _rand_qkv(jax.random.key(21), 1, S, S, H, K, 32)
    cot = jax.random.normal(jax.random.key(22), q.shape)
    if docs is None:
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        seg = jnp.ones((1, S), jnp.int32)
    else:
        pos, seg = _packed_row(docs, S)
    real = np.asarray(seg != 0)[0]
    # the oracle's padding rows are a uniform softmax over padding:
    # "don't care" in the loss, so they carry no cotangent here
    cot = cot * jnp.asarray(real)[None, :, None, None]

    def flash(ordered, q, k, v):
        return flash_attention(
            q, k, v, q_positions=pos, kv_positions=pos, q_segment_ids=seg,
            kv_segment_ids=seg, sliding_window=window, block_q=bq,
            block_kv=bkv, rows_ordered=ordered)

    def oracle(q, k, v):
        mask = make_attention_mask(pos, pos, seg, seg, causal=True,
                                   sliding_window=window)
        return dot_product_attention(q, k, v, mask)

    def out_and_grads(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return tuple(np.asarray(t) for t in (out, *vjp(cot)))

    return (out_and_grads(functools.partial(flash, True)),
            out_and_grads(functools.partial(flash, False)),
            out_and_grads(oracle), real)


@pytest.mark.parametrize("case", BAND_CASES)
def test_band_matches_oracle(case):
    banded, _, ref, real = _band_case(case)
    window, bq, bkv = BAND_CASES[case][:3]
    bands = fa.kernel_bands(512, 512, bq, bkv, causal=True, window=window,
                            rows_ordered=True)
    assert all(b.visited < b.rectangular for b in bands.values()), (
        "the case must cut the grid, or it tests nothing")
    for got, want, name in zip(banded, ref, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(got[:, real], want[:, real], atol=5e-5,
                                   rtol=5e-5, err_msg=f"{name} [{case}]")


@pytest.mark.parametrize("case", BAND_CASES)
def test_band_same_bits_as_full_grid(case):
    """At equal blocks the band leaves out only steps that added
    nothing, in the same order: out, dq, dk, dv are the full grid's to
    the bit. A clamped block whose body ran twice would show here."""
    banded, full, _, _ = _band_case(case)
    for got, want, name in zip(banded, full, ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{name} [{case}]")


def _unmasked_block_pairs(pos, seg, window, bq, bkv):
    """[n_q, n_kv] bool: the block pair holds a (query, key) pair that
    _block_mask keeps."""
    qp, kp = pos[:, None], pos[None, :]
    keep = (seg[:, None] == seg[None, :]) & (seg[None, :] != 0) & (kp <= qp)
    if window is not None:
        keep &= kp > qp - window
    S = len(pos)
    return keep.reshape(S // bq, bq, S // bkv, bkv).any(axis=(1, 3))


def _steps(band, i):
    """The inner blocks that the band's index map visits for outer
    block ``i`` with the body enabled, in grid order."""
    return [int(band.index(i, j)) for j in range(band.steps)
            if bool(band.inside(i, j))]


@pytest.mark.parametrize("window", [128, 700, None])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_band_holds_every_unmasked_pair(seed, window):
    """The band arithmetic alone, over rows that pack_examples deals
    from random document lengths: every block pair with an unmasked
    pair lies inside the band of all three kernels' index maps, each
    visited once; and what _block_live calls live outside the band
    (all-padding query blocks against later padding) holds no pair."""
    rng = np.random.default_rng(seed)
    S = 2048
    lens, room = [], S - int(rng.integers(0, 300))   # a padded tail
    while room > 8:
        lens.append(int(min(room, np.clip(rng.lognormal(5.5, 0.9), 8,
                                          1500))))
        room -= lens[-1]
    pos, seg = (np.asarray(a[0]) for a in _packed_row(lens, S))
    for bq, bkv in [(128, 128), (128, 256), (256, 128), (512, 512),
                    (256, 1024)]:
        has_pair = _unmasked_block_pairs(pos, seg, window, bq, bkv)
        bands = fa.kernel_bands(S, S, bq, bkv, causal=True, window=window,
                                rows_ordered=True)
        visited = np.zeros_like(has_pair)
        for i in range(S // bq):
            blocks = _steps(bands["fwd"], i)
            assert len(set(blocks)) == len(blocks), "a block run twice"
            visited[i, blocks] = True
        visited_t = np.zeros_like(has_pair)
        for j in range(S // bkv):
            blocks = _steps(bands["dkv"], j)
            assert len(set(blocks)) == len(blocks), "a block run twice"
            visited_t[blocks, j] = True
        np.testing.assert_array_equal(visited, visited_t)   # the transpose
        assert not (has_pair & ~visited).any(), (bq, bkv)
        assert visited.sum() == bands["fwd"].visited == bands["dkv"].visited
        for i, j in zip(*np.nonzero(~visited)):
            qs, ks = slice(i * bq, (i + 1) * bq), slice(j * bkv,
                                                        (j + 1) * bkv)
            if bool(fa._block_live(pos[qs], pos[ks], seg[qs], seg[ks],
                                   True, window)):
                assert not has_pair[i, j]


def test_band_extent_and_blocks_from_the_window():
    # the routed cell's window layers: 8192 packed, window 128
    bq, bkv, bands = fa.call_plan(8192, 8192, causal=True, window=128,
                                  rows_ordered=True)
    assert (bq, bkv) == (512, 512)
    for band in bands.values():
        old_grid = (8192 // 256) * (8192 // 1024)
        assert band.steps * (8192 // 512) * 4 <= old_grid
        assert band.visited * 4 <= band.rectangular
    # its full layers: the default blocks, the rectangle, the triangle
    bq, bkv, bands = fa.call_plan(8192, 8192, causal=True, window=None,
                                  rows_ordered=True)
    assert (bq, bkv) == (256, 1024)
    assert bands["fwd"].steps == 8 and bands["dkv"].steps == 32
    assert bands["fwd"].visited == 144 and bands["fwd"].rectangular == 256
    # the dense cell: one kv block a query block, the full grid's code
    bq, bkv, bands = fa.call_plan(1024, 1024, causal=True, window=4096,
                                  rows_ordered=True)
    assert (bq, bkv) == (256, 1024)
    for band in bands.values():
        assert band.geom is None and band.visited == band.rectangular
    assert bands["fwd"].steps == 1 and bands["dkv"].steps == 4
    # nothing stated, or not causal self-attention: the full grid
    for kw in (dict(causal=True, rows_ordered=False),
               dict(causal=False, rows_ordered=True)):
        bq, bkv, bands = fa.call_plan(8192, 8192, window=128, **kw)
        assert (bq, bkv) == (256, 1024)
        assert all(b.geom is None for b in bands.values())
    _, _, bands = fa.call_plan(1024, 2048, causal=True, window=128,
                               rows_ordered=True)
    assert all(b.geom is None for b in bands.values())


def _pallas_grids(jaxpr, found=None):
    """{kernel name: set of grids} of every pallas_call under ``jaxpr``."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], set()).add(
                tuple(eqn.params["grid_mapping"].grid))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_grids(sub, found)
    return found


def test_traced_grids_are_the_plan():
    """What call_plan reports (the step_build span's flash_grid) is what
    the three pallas_calls are built with."""
    q = jnp.zeros((1, 2048, 2, 32))

    def loss(q, k, v):
        return flash_attention(q, k, v, sliding_window=128,
                               rows_ordered=True).sum()

    grids = _pallas_grids(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        q, q, q).jaxpr)
    bq, bkv, bands = fa.call_plan(2048, 2048, causal=True, window=128,
                                  rows_ordered=True)
    assert (bq, bkv) == (512, 512)
    assert grids == {
        "flash_fwd": {(1, 2, 2048 // bq, bands["fwd"].steps)},
        "flash_dq": {(1, 2, 2048 // bq, bands["dq"].steps)},
        "flash_dkv": {(1, 2, 2048 // bkv, bands["dkv"].steps)}}
    assert bands["fwd"].steps == bands["dkv"].steps == 2


def test_ring_and_cache_prefill_keep_the_full_grid():
    """ops/ring_attention.py sees kv slices shifted around the ring and
    models/kvcache.py attends a cache: neither states rows_ordered, so
    both build the rectangular grid at the default blocks."""
    from gke_ray_train_tpu.models.config import ModelConfig
    from gke_ray_train_tpu.models.kvcache import forward_step, init_cache
    from gke_ray_train_tpu.models.transformer import init_params
    from gke_ray_train_tpu.ops.ring_attention import ring_attention
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=1, fsdp=1, model=1, context=2),
                      devices=jax.devices()[:2])
    q = jnp.zeros((1, 4096, 2, 32))
    grids = _pallas_grids(jax.make_jaxpr(
        lambda q: ring_attention(q, q, q, mesh=mesh, sliding_window=128))(
            q).jaxpr)
    assert grids == {"flash_fwd": {(1, 2, 2048 // 256, 2048 // 1024)}}

    cfg = ModelConfig(name="tiny", vocab_size=64, d_model=32, n_layers=1,
                      n_heads=2,
                      n_kv_heads=2, d_ff=64, max_seq_len=2048,
                      attn_impl="flash", block_pattern=("sliding",),
                      sliding_window=128, dtype="float32")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: init_cache(cfg, 1, 2048))
    grids = _pallas_grids(jax.make_jaxpr(
        lambda p, t, c, n: forward_step(p, t, cfg, c, n))(
            params, jnp.zeros((1, 2048), jnp.int32), cache,
            jnp.zeros((1,), jnp.int32)).jaxpr)
    assert grids == {"flash_fwd": {(1, 2, 2048 // 256, 2048 // 1024)}}
