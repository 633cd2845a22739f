import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from gke_ray_train_tpu.parallel.mesh import (
    MeshConfig, build_mesh, batch_sharding, MESH_AXES)
from gke_ray_train_tpu.parallel.sharding import (
    shard_tree, tree_shardings, pad_to_multiple)


def test_resolve_fill():
    cfg = MeshConfig(data=2, fsdp=-1).resolve(8)
    assert cfg.shape == (2, 4, 1, 1, 1)


def test_resolve_exact():
    cfg = MeshConfig(data=1, fsdp=2, model=2, context=2).resolve(8)
    assert cfg.shape == (1, 2, 2, 2, 1)


def test_resolve_errors():
    with pytest.raises(ValueError):
        MeshConfig(data=3, fsdp=-1).resolve(8)
    with pytest.raises(ValueError):
        MeshConfig(data=2, fsdp=2).resolve(8)
    with pytest.raises(ValueError):
        MeshConfig(data=-1, fsdp=-1).resolve(8)


def test_build_mesh_axes(fsdp_mesh):
    assert fsdp_mesh.axis_names == MESH_AXES
    assert fsdp_mesh.shape["data"] == 2
    assert fsdp_mesh.shape["fsdp"] == 4


def test_from_dict():
    cfg = MeshConfig.from_dict({"MESH_FSDP": 4, "MESH_MODEL": 2})
    assert cfg.fsdp == 4 and cfg.model == 2 and cfg.data == 1


def test_batch_sharding_places_batch(fsdp_mesh):
    x = jnp.zeros((16, 32))
    xs = jax.device_put(x, batch_sharding(fsdp_mesh))
    # batch axis split over data*fsdp = 8 shards
    assert xs.addressable_shards[0].data.shape == (2, 32)


def test_shard_tree(tp_mesh):
    tree = {"w": jnp.zeros((8, 16)), "b": jnp.zeros((16,))}
    specs = {"w": P("fsdp", "model"), "b": P(None)}
    sharded = shard_tree(tree, tp_mesh, specs)
    assert sharded["w"].addressable_shards[0].data.shape == (4, 8)
    assert sharded["b"].addressable_shards[0].data.shape == (16,)


def test_psum_over_mesh(dp_mesh):
    """A real collective on the fake mesh: mean over data axis."""
    from jax import shard_map

    def f(x):
        return jax.lax.pmean(x, "data")

    x = jnp.arange(8.0)
    y = shard_map(f, mesh=dp_mesh,
                  in_specs=P(("data",)), out_specs=P(("data",)))(x)
    np.testing.assert_allclose(np.asarray(y), np.full(8, 3.5))


def test_pad_to_multiple():
    assert pad_to_multiple(100, 128) == 128
    assert pad_to_multiple(256, 128) == 256


def test_multislice_hybrid_mesh_data_outermost():
    """num_slices=2 (SURVEY.md §5.8 DCN): mesh builds on fake devices via
    the emulation fallback; slice blocks are contiguous and the data axis
    rides across them (only batch psums cross DCN)."""
    import jax
    import numpy as np
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh

    devices = jax.devices()[:8]
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2, context=1,
                                 num_slices=2), devices)
    assert dict(mesh.shape) == {"data": 2, "fsdp": 2, "model": 2,
                                "context": 1, "pipe": 1}
    # data index 0 ↔ first contiguous half (slice 0), index 1 ↔ second
    got0 = [d.id for d in mesh.devices[0].flatten()]
    got1 = [d.id for d in mesh.devices[1].flatten()]
    assert sorted(got0) == [d.id for d in devices[:4]]
    assert sorted(got1) == [d.id for d in devices[4:]]


def test_multislice_validation():
    import jax
    import pytest
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    with pytest.raises(ValueError, match="divisible by"):
        build_mesh(MeshConfig(data=3, fsdp=1, model=1, context=1,
                              num_slices=2), jax.devices()[:3])


def test_multislice_train_step_runs():
    """Full sharded train step over the hybrid mesh (the dryrun variant's
    core, minus the subprocess)."""
    import jax
    import jax.numpy as jnp
    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    from gke_ray_train_tpu.parallel.placement import make_place_batch
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)
    import numpy as np

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, model=2, context=1,
                                 num_slices=2), jax.devices()[:8])
    cfg = tiny(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32")
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, grad_accum=2)
    place = make_place_batch(mesh)
    b = {
        "inputs": np.ones((8, 16), np.int32),
        "targets": np.ones((8, 16), np.int32),
        "weights": np.ones((8, 16), np.float32),
    }
    state, m = step(state, place(b))
    assert jnp.isfinite(m["loss"])


class _FakeSliceDevice:
    """A CPU device wearing a ``slice_index`` — drives build_mesh down
    the REAL create_hybrid_device_mesh path (the one actual multi-slice
    TPU hardware takes) with no TPU attached."""

    def __init__(self, dev, slice_index):
        self._dev = dev
        self.slice_index = slice_index

    def __getattr__(self, name):
        return getattr(self._dev, name)

    def __repr__(self):
        return f"FakeSliceDev(id={self._dev.id}, slice={self.slice_index})"


def _slice_ids(mesh):
    import numpy as np
    return np.vectorize(lambda d: d.slice_index)(mesh.devices)


def test_multislice_data_axis_spans_dcn_contract(devices):
    """VERDICT open item 7, pinned: on a multi-slice mesh the `data`
    axis — and ONLY the `data` axis — crosses slice (DCN) boundaries;
    fsdp/model/context/pipe traffic stays intra-slice (ICI). A mesh
    refactor that silently puts FSDP all-gathers on DCN fails here."""
    from gke_ray_train_tpu.parallel.mesh import (
        MESH_AXES, MeshConfig, build_mesh)

    fake = [_FakeSliceDevice(d, d.id // 4) for d in devices]
    for shape in (dict(data=2, fsdp=4), dict(data=2, fsdp=2, model=2),
                  dict(data=2, fsdp=1, model=2, context=2)):
        mesh = build_mesh(MeshConfig(num_slices=2, **shape), fake)
        sl = _slice_ids(mesh)
        data_ax = MESH_AXES.index("data")
        # slice id must be CONSTANT along every non-data axis...
        for ax, name in enumerate(MESH_AXES):
            if name == "data":
                continue
            assert (sl == sl.take([0], axis=ax)).all(), (
                f"{shape}: axis {name!r} crosses slice boundaries — "
                f"its collectives would ride DCN\n{sl}")
        # ...and the data axis must actually SPAN the slices
        # (slice-id-major: one contiguous block of data coords per
        # slice, so only batch-gradient reduction crosses DCN)
        spans = {tuple(sl.take(i, axis=data_ax).ravel().tolist())
                 for i in range(sl.shape[data_ax])}
        assert len(spans) == 2, f"{shape}: data axis does not span DCN"
        for block in spans:
            assert len(set(block)) == 1, (
                f"{shape}: a data coordinate mixes slices {block}")


def test_multislice_emulated_layout_same_contract(devices, caplog):
    """The fake/CPU fallback (no slice_index attr) must emulate the
    same DCN-outermost layout: contiguous device blocks act as slices,
    spanned only by `data`."""
    import logging
    import numpy as np
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh

    with caplog.at_level(logging.WARNING):
        mesh = build_mesh(MeshConfig(data=2, fsdp=4, num_slices=2),
                          devices)
    assert any("no slice_index" in r.message for r in caplog.records)
    # emulated slice id: contiguous blocks of the given device order
    order = {d.id: i for i, d in enumerate(devices)}
    sl = np.vectorize(lambda d: order[d.id] // 4)(mesh.devices)
    assert (sl[0] == 0).all() and (sl[1] == 1).all(), sl
