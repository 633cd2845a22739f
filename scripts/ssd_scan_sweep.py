"""State-space scan sweep on the attached TPU (ROADMAP R10; PERF.md
section 6, PR 33).

One packed row of 8192 with 8 documents, 128 heads of 64, state 128, one
group, chunks of 256, bf16: a mixer of the hybrid cell. Times, each
jitted alone, ``ops/ssm.py::ssd_scan`` forward and forward + backward
(all six gradients) in its ``jax.numpy`` form and as the kernel pair at
each candidate (heads a grid step; the chunk's sub-tiles of 128 with
the tiles above the diagonal skipped, or the whole chunk as one tile),
the kernels alone (``ssd_fwd``; ``ssd_states`` + ``ssd_bwd``), and the
two head-major copies of x and y that head-major blocks would need
(``head_major_copies``). ``ops/ssm.py::HEADS_A_STEP``
and ``SUB_TILE`` hold what was read off this table. Not a cell: nothing
here is an end-to-end number.

    python scripts/ssd_scan_sweep.py            # through the chip tool
    python scripts/ssd_scan_sweep.py --compile  # no chip: compile for a
                                                # described v5e only
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
import numpy as np  # noqa: E402

B, S, H, P, N, G, CHUNK = 1, 8192, 128, 64, 128, 1, 256
ARMS = [  # (form, heads a grid step or a block, sub-tile)
    ("xla", 16, 0),
    ("pallas", 16, 128), ("pallas", 8, 128), ("pallas", 32, 128),
    ("pallas", 16, 256),
]


def packed_segments(seed: int) -> np.ndarray:
    """segment ids [1, S]: 8 documents, no boundary at a multiple of the
    chunk."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, S // 8) * 8 + 3, 7, replace=False))
    return (np.searchsorted(cuts, np.arange(S), side="right") + 1
            ).astype(np.int32)[None]


def operands(spec=None):
    # x and the cotangent as the mixer has them, heads side by side
    shapes = dict(x=(B, S, H * P), dt=(B, S, H), a=(H,), b=(B, S, G, N),
                  c=(B, S, G, N), d=(H,), g=(B, S, H * P))
    f32 = ("dt", "a", "d")
    if spec is not None:
        ops = {n: spec(s, jnp.float32 if n in f32 else jnp.bfloat16)
               for n, s in shapes.items()}
        ops["seg"] = spec((B, S), jnp.int32)
        return ops
    keys = dict(zip(shapes, jax.random.split(jax.random.key(33), 7)))
    ops = {n: jax.random.normal(keys[n], s, jnp.float32)
           for n, s in shapes.items()}
    # real decays: A = -U[1, 16], dt log-uniform in [0.001, 0.1]
    ops["a"] = -jnp.exp(jax.random.uniform(
        keys["a"], (H,), minval=0.0, maxval=np.log(16.0)))
    ops["dt"] = jnp.exp(jax.random.uniform(
        keys["dt"], (B, S, H), minval=np.log(1e-3), maxval=np.log(0.1)))
    ops["d"] = jnp.ones((H,), jnp.float32)
    for n in shapes:
        if n not in f32:
            ops[n] = ops[n].astype(jnp.bfloat16)
    ops["seg"] = jnp.asarray(packed_segments(33))
    return ops


def candidates(form, heads, tile):
    """{name: fn(ops)}: the scan forward, forward + backward, and for
    the kernel pair each kernel alone."""
    from gke_ray_train_tpu.ops import ssm
    if tile:
        ssm.SUB_TILE = tile
    plan = ssm.scan_plan(S, CHUNK, H, P, N, G, heads)
    if form == "xla":
        plan = ssm.ScanPlan("xla", plan.chunk, plan.chunks, heads)
    assert plan.head_block == heads, plan
    run = ssm._scan_xla if form == "xla" else functools.partial(
        ssm._scan_pallas, interpret=False)

    def scan(o):
        return run(o["x"].reshape(B, S, H, P), o["dt"], o["a"], o["b"],
                   o["c"], o["d"], o["seg"], plan).reshape(B, S, H * P)

    def both(o):
        names = ("x", "dt", "a", "b", "c", "d")
        y, vjp = jax.vjp(lambda *t: scan({**o, **dict(zip(names, t))}),
                         *(o[n] for n in names))
        return y, vjp(o["g"])
    out = {"fwd": scan, "fwd_bwd": both}
    if form == "pallas":
        sz = ssm._Sizes(Q=plan.chunk, hb=heads, P=P, N=N, per_group=H // G,
                        tq=tile)

        def inputs(o):
            cs = jnp.cumsum((o["dt"] * o["a"]).reshape(B, -1, CHUNK, H),
                            axis=2).reshape(B, S, H)
            return (o["x"], o["dt"], cs, o["b"].reshape(B, S, G * N),
                    o["c"].reshape(B, S, G * N), o["d"], o["seg"])
        out["ssd_fwd"] = lambda o: ssm._chunks_fwd(
            *inputs(o), sz=sz, interpret=False)
        # ssd_states + ssd_bwd, and the sums that turn their partial
        # results into the six gradients
        out["ssd_bwd"] = lambda o: ssm._chunks_bwd(
            *inputs(o), o["g"], sz=sz, interpret=False)
    return out


def head_major_copies(o):
    """The two copies that head-major blocks would need (x in, y out; a
    barrier between, so that XLA makes both)."""
    def head_major(t):
        return jnp.moveaxis(t.reshape(B, S // CHUNK, CHUNK, H, P), 2, 3)
    y = jax.lax.optimization_barrier(head_major(o["x"]))
    return jnp.moveaxis(y, 3, 2).reshape(B, S, H * P)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/ssd_scan_sweep.json")
    ap.add_argument("--arms", type=json.loads, default=ARMS,
                    help="JSON [[form, heads, sub-tile], ...]; xla first")
    args = ap.parse_args()

    if args.compile:
        os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1)).devices[0]
        sh = SingleDeviceSharding(dev)

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
        ops = operands(spec)
    else:
        ops = operands()

    def measure(row, name, fn, reference=None):
        jitted = jax.jit(fn)
        try:
            if args.compile:
                t0 = time.perf_counter()
                built = jitted.lower(ops).compile()
                row[name] = "compiles"
                row[name + "_compile_s"] = round(time.perf_counter() - t0, 1)
                row[name + "_temp_mb"] = round(
                    built.memory_analysis().temp_size_in_bytes / 1e6, 1)
                return None
            res = jax.block_until_ready(jitted(ops))
            if reference is not None:
                # against the jax.numpy form, relative to its largest value
                row[name + "_gap"] = [
                    round(float(jnp.max(jnp.abs(
                        a.astype(jnp.float32) - b.astype(jnp.float32)))
                        / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-30)),
                        5)
                    for a, b in zip(jax.tree.leaves(res),
                                    jax.tree.leaves(reference))]
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = jitted(ops)
            jax.block_until_ready(out)
            row[name + "_ms"] = round(
                (time.perf_counter() - t0) / args.iters * 1e3, 3)
            return res
        except Exception as e:  # noqa: BLE001 - the table's point
            row[name] = f"{type(e).__name__}: {str(e)[:600]}"
            return None

    rows = []
    reference = {}
    for form, heads, tile in args.arms:
        row = {"form": form, "heads": heads, "sub_tile": tile}
        for name, fn in candidates(form, heads, tile).items():
            res = measure(row, name, fn, reference.get(name))
            if form == "xla":
                reference[name] = res
        print(json.dumps(row), flush=True)
        rows.append(row)
    row = {"form": "beside"}
    measure(row, "head_major_copies", head_major_copies)
    print(json.dumps(row), flush=True)
    rows.append(row)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "compile_only": args.compile,
                   "shape": [B, S, H, P, N, G, CHUNK], "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
