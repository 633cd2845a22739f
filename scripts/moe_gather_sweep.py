"""The routed layer's gather-and-sum on the attached TPU (PERF.md
section 6, PR 35): ``y[t] = sum_k w[t, k] buf[row[t, k]]`` at the three
routed cells' shapes, for both of its uses.

- ``combine``: ``moe/combine``'s forward. ``buf`` is the experts' output
  with the rows past the live ones zeroed, ``w`` the routing weights
  rounded to bf16, 0 where a pick is not held.
- ``dispatch_bwd``: ``moe/dispatch``'s backward. ``buf`` is the sorted
  rows' cotangent (two products' dx, added), ``w`` the held mask.

Each arm is jitted with the producer of ``buf`` as the step has it, so
that a form that wants ``buf`` in another layout pays for it or has it
fused; ``producer`` times that producer alone. Arms: ``today`` (the
expressions the layer had before PR 35), ``unrolled`` (the same sum
unrolled over K in ``jax.numpy``, ``ops/moe.py::_gather_sum_xla``) and
``pallas`` at several token tiles (the kernel ``moe_gather_sum``). The
buffer's rows are sorted by expert as ``routed_experts`` sorts them. It
prints ms a call and the share of HBM's 819 GB/s that reading every
pick's row and writing ``[T, D]`` once would take.
``ops/moe.py::gather_plan`` holds what was read off this table. Not a
cell: nothing here is an end-to-end number.

    python scripts/moe_gather_sweep.py            # through the chip tool
    python scripts/moe_gather_sweep.py --compile  # no chip: compile for a
                                                  # described v5e only
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gke_ray_train_tpu.ops import moe  # noqa: E402

# (cell, tokens, picks, width, router outputs, held)
SHAPES = [
    ("hybrid", 8192, 10, 4096, 72, 18),
    ("routed", 8192, 8, 6144, 128, 16),
    ("latent", 8192, 4, 2048, 64, 16),
]
TILES = (16, 32, 64, 128, 256)
HBM_BYTES_S = 819e9


def operands(tokens, picks, width, experts, held, spec=None):
    """(buf, second buf, row, w, held mask, live rows) as the layer has
    them: a token's picks are distinct experts, the held ones sorted by
    expert to the front of the buffer."""
    rows = tokens * picks
    if spec is not None:
        return (spec((rows, width), jnp.bfloat16),
                spec((rows, width), jnp.bfloat16),
                spec((tokens, picks), jnp.int32),
                spec((tokens, picks), jnp.bfloat16),
                spec((tokens, picks), jnp.bool_), spec((), jnp.int32))
    rng = np.random.default_rng(35)
    idx = np.argsort(rng.random((tokens, experts)), axis=1)[:, :picks]
    mask = idx < held
    e = np.where(mask, idx, held).reshape(-1)
    order = np.argsort(e, kind="stable")
    row = np.empty(rows, np.int32)
    row[order] = np.arange(rows, dtype=np.int32)
    w = rng.random((tokens, picks)).astype(np.float32) * mask
    keys = jax.random.split(jax.random.key(35), 2)
    return (jax.random.normal(keys[0], (rows, width), jnp.bfloat16),
            jax.random.normal(keys[1], (rows, width), jnp.bfloat16),
            jnp.asarray(row.reshape(tokens, picks)),
            jnp.asarray(w, jnp.bfloat16), jnp.asarray(mask),
            jnp.asarray(int(mask.sum()), jnp.int32))


def producers():
    """{use: fn(ops) -> buf}: the operation that makes ``buf`` in the
    step."""
    def combine(o):
        a, _, _, _, _, live = o
        return jnp.where((jnp.arange(a.shape[0]) < live)[:, None], a, 0)

    def dispatch_bwd(o):
        return o[0] + o[1]
    return {"combine": combine, "dispatch_bwd": dispatch_bwd}


def forms(use, picks, tiles):
    """{arm: fn(buf, ops) -> [T, D]}."""
    def today(buf, o):
        _, _, row, w, held, _ = o
        if use == "combine":
            return jnp.einsum("tkd,tk->td", buf[row], w.astype(buf.dtype),
                              preferred_element_type=jnp.float32
                              ).astype(buf.dtype)
        return jnp.sum(jnp.where(held[..., None], buf[row], 0), axis=1,
                       dtype=jnp.float32).astype(buf.dtype)

    def weights(o):
        return o[3] if use == "combine" else o[4]

    out = {"today": today,
           "unrolled": lambda buf, o: moe.gather_sum(
               buf, o[2], weights(o), plan=moe.GatherPlan("xla", 0))}
    for tile in tiles:
        out[f"pallas_{tile}"] = (
            lambda buf, o, tile=tile: moe.gather_sum(
                buf, o[2], weights(o), plan=moe.GatherPlan("pallas", tile),
                interpret=False))
    return out


def kernel_alone(tile):
    """The kernel on a buffer that is already ``[P, D / 128, 128]``: no
    producer, no change of layout on either side."""
    def run(o):
        _, _, row, w, _, _ = o[:6]
        return moe._gather_sum_pallas(o[6], row, w.astype(jnp.float32),
                                      tile=tile, interpret=False)
    return run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tiles", type=json.loads, default=list(TILES))
    ap.add_argument("--out", default="chiprun_out/moe_gather_sweep.json")
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

    def measure(row, name, fn, ops, reference=None):
        jitted = jax.jit(fn)
        try:
            if args.compile:
                t0 = time.perf_counter()
                built = jitted.lower(ops).compile()
                row[name + "_compile_s"] = round(time.perf_counter() - t0, 1)
                row[name + "_temp_mb"] = round(
                    built.memory_analysis().temp_size_in_bytes / 1e6, 1)
                return None
            res = jax.block_until_ready(jitted(ops))
            if reference is not None:
                a, b = (x.astype(jnp.float32) for x in (res, reference))
                row[name + "_gap"] = float(jnp.max(jnp.abs(a - b)) / (
                    jnp.max(jnp.abs(b)) + 1e-30))
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
    for cell, tokens, picks, width, experts, held in SHAPES:
        ops = operands(tokens, picks, width, experts, held, spec)
        tiles = [t for t in args.tiles if tokens % t == 0
                 and 2 * picks * t * width * 2 <= 64 * 2**20]
        floor_ms = (tokens * picks + tokens) * width * 2 / HBM_BYTES_S * 1e3
        for use, make in producers().items():
            row = {"cell": cell, "use": use, "tokens": tokens,
                   "picks": picks, "width": width,
                   "plan": moe.gather_plan(tokens * picks, width, picks,
                                           jnp.bfloat16)._asdict(),
                   "floor_ms": round(floor_ms, 3)}
            measure(row, "producer", make, ops)
            reference = None
            for arm, form in forms(use, picks, tiles).items():
                res = measure(row, arm, lambda o, form=form, make=make:
                              form(make(o), o), ops, reference)
                if arm == "today":
                    reference = res
                if f"{arm}_ms" in row and "producer_ms" in row:
                    alone = row[f"{arm}_ms"] - row["producer_ms"]
                    row[f"{arm}_hbm_share"] = round(
                        floor_ms / max(alone, 1e-6), 3)
            if use == "combine":
                shaped = (spec((tokens * picks, width // 128, 128),
                               jnp.bfloat16) if spec else
                          ops[0].reshape(tokens * picks, width // 128, 128))
                for tile in tiles:
                    measure(row, f"kernel_alone_{tile}", kernel_alone(tile),
                            ops + (shaped,))
            print(json.dumps(row), flush=True)
            rows.append(row)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "compile_only": args.compile, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
