"""The frozen NF4 products on the attached TPU (PERF.md section 6):
``x [rows, depth] @ dequantize(qt) [depth, cols]`` and its ``dx``,
``dy [rows, cols] @ dequantize(qt)^T``, at every base-projection shape
of the four training cells, at their rows a call (2048 in the dense
cell, one packed row of 8192 in the others), and at a serving decode's
32 rows.

Arms, each run ``--iters`` times inside one jitted loop (so that a call
of tens of microseconds is not read as the host's dispatch):

- ``product``: the bf16 product alone, over a weight already decoded.
- ``xla`` / ``xla_dx``: what the step runs without the kernel:
  ``ops/quant.py::dequantize`` (one fusion) and the product after it.
- ``kernel_<rows>_<cols>_<depth>`` / ``dx_...``: the kernel pair
  ``nf4_matmul`` / ``nf4_matmul_dx`` at that tile (rows, columns,
  contraction), ``*_gap`` its largest difference from the ``xla`` arm
  over the largest magnitude; ``piped_...``: the same with the next
  weight tile decoded while this one is in the MXU.

It prints ms a call and the share of the MXU's 197 TFLOP/s that the
product's 2 x rows x depth x cols operations would take, and once,
``decode_mismatches``: the weights a kernel that only decodes writes
that differ from ``dequantize``'s in any bit. ``ops/quant.py::
nf4_matmul_plan`` holds what was read off this table. Not a cell:
nothing here is an end-to-end number.

    python scripts/nf4_matmul_sweep.py --out <file.json>  # on the chip
    python scripts/nf4_matmul_sweep.py --compile --out <file.json>
                                  # no chip: compile for a described v5e
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from gke_ray_train_tpu.ops import quant  # noqa: E402

# tiles (rows, cols, contraction) tried at each rows a call
TILES = {
    2048: [(2048, 1024, 512), (2048, 2048, 512), (2048, 512, 1024),
           (2048, 1024, 1024)],
    8192: [(2048, 1024, 512), (4096, 512, 512)],
    32: [(32, 1024, 512), (32, 2048, 1024)],
}
# (cell, rows a call, depth, cols, calls a micro-pass, tiles): the
# stacked NF4 projections of each cell's configuration, those of 1% of
# its step or more
SHAPES = [
    ("dense", 2048, 4096, 14336, 64, TILES[2048]),
    ("dense", 2048, 14336, 4096, 32, TILES[2048]),
    ("dense", 2048, 4096, 4096, 64, TILES[2048]),
    ("dense", 2048, 4096, 1024, 64, TILES[2048]),
    ("routed", 8192, 6144, 8192, 8, TILES[8192]),
    ("routed", 8192, 6144, 2048, 14, TILES[8192]),
    ("latent", 8192, 2048, 1536, 92, TILES[8192]),
    ("latent", 8192, 5120, 2048, 47, TILES[8192]),
    ("hybrid", 8192, 4096, 16768, 18, [(2048, 128, 512)]),
    ("hybrid", 8192, 8192, 4096, 18, TILES[8192]),
    ("hybrid", 8192, 4096, 1536, 40, TILES[8192]),
    ("serve", 32, 4096, 14336, 64, TILES[32]),
]
# the tiles the pipelined kernels are tried at (wider ones exceed VMEM)
PIPED = ((2048, 1024, 512), (2048, 512, 1024))
MXU_FLOPS = 197e12


def weights(depth, cols, spec=None):
    if spec is not None:
        return quant.QTensor(spec((depth, cols), jnp.uint4),
                             spec((depth // 64, cols), jnp.float32))
    w = jax.random.normal(jax.random.key(38), (depth, cols),
                          jnp.float32) * 0.02
    return quant.quantize_tensor(w, "nf4")


def looped(fn, iters):
    """``fn(a, qt)`` ``iters`` times in one program, each call's input
    changed in one element by the last output, so that nothing is
    hoisted out of the loop, and each output whole behind a barrier."""
    def run(a, qt):
        def body(_, carry):
            a, s = carry
            # the barrier keeps XLA from computing one element only
            out = jax.lax.optimization_barrier(fn(a, qt))
            s = s + out[0, 0].astype(jnp.float32)
            return a.at[0, 0].set(s.astype(a.dtype)), s
        return jax.lax.fori_loop(0, iters, body, (a, jnp.float32(0)))[1]
    return jax.jit(run)


def _pipelined(dx):
    """The kernel with the NEXT weight tile decoded while this one is in
    the MXU, into the other of two VMEM slots: the decode and the product
    of one grid step do not depend on each other, so the scheduler may
    interleave them. The first step of a row block decodes its own tile
    first; the last decodes a tile no step uses."""
    def kernel(a_ref, c_now, s_now, c_next, s_next, out_ref, acc_ref, w_ref,
               *, group):
        j, k = pl.program_id(1), pl.program_id(2)
        nk = pl.num_programs(2)
        t = j * nk + k
        slot = t % 2

        @pl.when(t == 0)
        def _():
            w_ref[0] = quant._decode_tile(c_now[...], s_now[...], group,
                                          w_ref.dtype)

        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        w_ref[1 - slot] = quant._decode_tile(c_next[...], s_next[...], group,
                                             w_ref.dtype)
        if dx:
            acc_ref[...] += jax.lax.dot_general(
                a_ref[...], w_ref[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            acc_ref[...] += jnp.dot(a_ref[...], w_ref[slot],
                                    preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)
    return kernel


def _call_pipelined(a, qt, tile, dx):
    plan = quant.Nf4Plan("pallas", *tile, *tile[1:])
    specs = quant._nf4_specs(a, qt.codes, qt.scales, plan, dx=dx)
    _, w_spec, s_spec = specs["in_specs"]
    grid = specs["grid"]
    nj, nk = grid[1], grid[2]

    def nxt(at):
        def index(i, j, k):
            last = (j == nj - 1) & (k == nk - 1)
            jn = jnp.where(last, j, j + (k + 1) // nk)
            kn = jnp.where(last, k, (k + 1) % nk)
            return at(i, jn, kn)
        return index
    tk, tn = w_spec.block_shape
    specs["in_specs"] = specs["in_specs"] + [
        pl.BlockSpec(w_spec.block_shape, nxt(w_spec.index_map)),
        pl.BlockSpec(s_spec.block_shape, nxt(s_spec.index_map))]
    specs["scratch_shapes"] = specs["scratch_shapes"] + [
        pltpu.VMEM((2, tk, tn), a.dtype)]
    # the second pair of weight blocks and the two slots
    specs["compiler_params"] = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=specs["compiler_params"].vmem_limit_bytes
        + 2 * tk * tn + 4 * tk * tn * a.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_pipelined(dx), group=64), **specs,
        name="nf4_matmul_dx" if dx else "nf4_matmul",
    )(a, qt.codes, qt.scales, qt.codes, qt.scales)


def arms(tiles):
    """{arm: (operand side, fn(a, qt))}: XLA's form, the bf16 product
    alone, the kernels at each tile and the pipelined kernels at the
    tiles of :data:`PIPED`."""
    def xla(x, qt):
        return jnp.einsum("md,dn->mn", x, quant.dequantize(qt, x.dtype))

    def xla_dx(dy, qt):
        return jnp.einsum("mn,dn->md", dy, quant.dequantize(qt, dy.dtype))

    def product(x, w):
        return x @ w

    out = {"xla": ("x", xla), "xla_dx": ("dy", xla_dx),
           "product": ("x", product)}
    for tile in tiles:
        # the same weight tile both ways
        plan = quant.Nf4Plan("pallas", *tile, *tile[1:])
        tag = "_".join(map(str, tile))
        out[f"kernel_{tag}"] = ("x", functools.partial(
            lambda x, qt, plan: quant._nf4_fwd_pallas(
                x, qt.codes, qt.scales, plan=plan, interpret=False),
            plan=plan))
        out[f"dx_{tag}"] = ("dy", functools.partial(
            lambda dy, qt, plan: quant._nf4_dx_pallas(
                dy, qt.codes, qt.scales, plan=plan, interpret=False),
            plan=plan))
        if tile in PIPED:
            out[f"piped_{tag}"] = ("x", functools.partial(
                _call_pipelined, tile=tile, dx=False))
            out[f"piped_dx_{tag}"] = ("dy", functools.partial(
                _call_pipelined, tile=tile, dx=True))
    return out


def decode_mismatches(qt, tile):
    """Weights the kernels' decode writes, a tile at a time, against
    ``dequantize``'s, in any bit."""
    depth, cols = qt.shape
    tk, tn = tile

    def kernel(c_ref, s_ref, o_ref):
        o_ref[...] = quant._decode_tile(c_ref[...], s_ref[...], 64,
                                        o_ref.dtype)
    decoded = pl.pallas_call(
        kernel, grid=(depth // tk, cols // tn),
        in_specs=[pl.BlockSpec((tk, tn), lambda i, j: (i, j)),
                  pl.BlockSpec((tk // 64, tn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tk, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((depth, cols), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2**20),
    )(qt.codes, qt.scales)
    want = jax.jit(lambda q: quant.dequantize(q, jnp.bfloat16))(qt)
    same = jax.lax.bitcast_convert_type(decoded, jnp.uint16) \
        == jax.lax.bitcast_convert_type(want, jnp.uint16)
    return int(jnp.sum(~same))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--cells", type=json.loads, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    spec = None
    if args.compile:
        os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1)).devices[0]

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=SingleDeviceSharding(dev))

    rows_out = []
    for cell, rows, depth, cols, calls, tried in SHAPES:
        if args.cells and cell not in args.cells:
            continue
        qt = weights(depth, cols, spec)
        if spec is not None:
            x, dy = spec((rows, depth), jnp.bfloat16), \
                spec((rows, cols), jnp.bfloat16)
            w = spec((depth, cols), jnp.bfloat16)
        else:
            keys = jax.random.split(jax.random.key(1), 2)
            x = jax.random.normal(keys[0], (rows, depth), jnp.bfloat16)
            dy = jax.random.normal(keys[1], (rows, cols), jnp.bfloat16)
            w = quant.dequantize(qt, jnp.bfloat16)
        least_ms = 2 * rows * depth * cols / MXU_FLOPS * 1e3
        row = {"cell": cell, "rows": rows, "depth": depth, "cols": cols,
               "calls": calls, "least_ms": round(least_ms, 4),
               "plan": quant.nf4_matmul_plan(rows, depth, cols,
                                             "nf4")._asdict()}
        tiles = [t for t in tried if rows % t[0] == 0
                 and cols % t[1] == 0 and depth % t[2] == 0]
        reference = {}
        for name, (side, fn) in arms(tiles).items():
            a = x if side == "x" else dy
            operand = w if name == "product" else qt
            jitted = looped(fn, args.iters)
            try:
                if args.compile:
                    t0 = time.perf_counter()
                    built = jitted.lower(a, operand).compile()
                    row[name + "_compile_s"] = round(
                        time.perf_counter() - t0, 2)
                    row[name + "_temp_mb"] = round(
                        built.memory_analysis().temp_size_in_bytes / 1e6, 1)
                    continue
                one = jax.jit(fn)(a, operand)
                if name in ("xla", "xla_dx"):
                    reference[side] = one
                elif side in reference:
                    ref = reference[side].astype(jnp.float32)
                    row[name + "_gap"] = float(
                        jnp.max(jnp.abs(one.astype(jnp.float32) - ref))
                        / (jnp.max(jnp.abs(ref)) + 1e-30))
                jax.block_until_ready(jitted(a, operand))
                t0 = time.perf_counter()
                jax.block_until_ready(jitted(a, operand))
                ms = (time.perf_counter() - t0) / args.iters * 1e3
                row[name + "_ms"] = round(ms, 4)
                row[name + "_mxu_share"] = round(least_ms / ms, 3)
            except Exception as e:  # noqa: BLE001 - the table's point
                row[name] = f"{type(e).__name__}: {str(e)[:600]}"
        if not args.compile and not rows_out:
            try:
                row["decode_mismatches"] = decode_mismatches(qt, (512, 256))
            except Exception as e:  # noqa: BLE001 - the table's point
                row["decode_mismatches"] = \
                    f"{type(e).__name__}: {str(e)[:600]}"
        print(json.dumps(row), flush=True)
        rows_out.append(row)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "compile_only": args.compile, "rows": rows_out}, f,
                  indent=1)


if __name__ == "__main__":
    main()
