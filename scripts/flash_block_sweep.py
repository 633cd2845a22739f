"""Flash attention block sweep on the attached TPU (ROADMAP S4).

Times the three kernels one by one (``flash_fwd``, ``flash_dq``,
``flash_dkv``: each jitted alone, so that XLA removes the other calls)
at one packed row of 8192, bf16; by default 64 query heads over 8 kv
heads of 128: the window-128 layers and the full layers of the routed
cell (``--heads 20 --kv-heads 20 --head-dim 256``: the latent layers of
the GLM cell, whose assembled q, k and v have 20 ungrouped heads of 256).
Arms: the full grid at the default blocks (what the kernels did before
the band), and the band at ``block_q`` in {256, 512} x ``block_kv`` in
{128, 256, 512, 1024}. ``ops/flash_attention.py::window_blocks`` holds
the rule read off this table (PERF.md section 6). Not a cell: nothing
here is an end-to-end number.

    python scripts/flash_block_sweep.py            # through the chip tool
    python scripts/flash_block_sweep.py --compile  # no chip: compile for a
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

B, S, H, K, DH = 1, 8192, 64, 8, 128
ARMS = [  # (window, rows_ordered, block_q, block_kv)
    (128, False, 256, 1024),
    *[(128, True, bq, bkv) for bq in (256, 512)
      for bkv in (128, 256, 512, 1024)],
    (None, False, 256, 1024),
    *[(None, True, bq, bkv) for bq in (256, 512) for bkv in (512, 1024)],
]


def packed_row(seed: int):
    """positions / segment ids [1, S] of one packed row: documents of
    lognormal length (median 720, 64-4096) dealt by pack_examples."""
    from gke_ray_train_tpu.data.packing import pack_examples
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.lognormal(np.log(720), 0.8, 64), 64, 4096).astype(int)
    docs = [{"input_ids": np.ones(n + 1, np.int32),
             "loss_weights": np.ones(n + 1, np.float32)} for n in lens]
    row = next(pack_examples(docs, S))
    return (jnp.asarray(row["positions"]).reshape(1, 1, S),
            jnp.asarray(row["segment_ids"]).reshape(1, 1, S))


def kernels(window, ordered, bq, bkv):
    """{name: fn(q, k, v, pos, seg, out, lse, g)} with one Pallas call
    each on the transposed [B, H, S, dh] layout."""
    from gke_ray_train_tpu.ops import flash_attention as fa
    kw = dict(scale=DH ** -0.5, causal=True, window=window, softcap=None,
              block_q=bq, block_kv=bkv, interpret=False,
              rows_ordered=ordered)

    def fwd(q, k, v, pos, seg, out, lse, g):
        return fa._fwd(q, k, v, pos, pos, seg, seg, **kw)

    def bwd(pick, q, k, v, pos, seg, out, lse, g):
        grads = fa._bwd((q, k, v, out, lse, pos, pos, seg, seg), g, **kw)
        return pick(grads)

    return {"flash_fwd": fwd,
            "flash_dq": functools.partial(bwd, lambda g: g[0]),
            "flash_dkv": functools.partial(bwd, lambda g: g[1:])}


def main() -> None:
    global H, K, DH
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--heads", type=int, default=H)
    ap.add_argument("--kv-heads", type=int, default=K)
    ap.add_argument("--head-dim", type=int, default=DH)
    ap.add_argument("--out", default="chiprun_out/flash_block_sweep.json")
    ap.add_argument("--arms", type=json.loads, default=ARMS,
                    help="JSON [[window, band, block_q, block_kv], ...]; "
                    "a window's full-grid arm first")
    args = ap.parse_args()
    H, K, DH = args.heads, args.kv_heads, args.head_dim

    shapes = dict(q=(B, H, S, DH), k=(B, K, S, DH), v=(B, K, S, DH),
                  out=(B, H, S, DH), g=(B, H, S, DH))
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
        ops = {n: spec(s, jnp.bfloat16) for n, s in shapes.items()}
        ops.update(pos=spec((B, 1, S), jnp.int32),
                   seg=spec((B, 1, S), jnp.int32),
                   lse=spec((B, H, 1, S), jnp.float32))
    else:
        keys = jax.random.split(jax.random.key(27), len(shapes))
        ops = {n: jax.random.normal(kk, s, jnp.bfloat16)
               for kk, (n, s) in zip(keys, shapes.items())}
        ops["pos"], ops["seg"] = packed_row(27)
        ops["lse"] = None
    order = ("q", "k", "v", "pos", "seg", "out", "lse", "g")

    rows = []
    full_grid = {}   # (window, kernel) -> the full-grid arm's result
    for window, ordered, bq, bkv in args.arms:
        row = {"window": window, "band": ordered, "block_q": bq,
               "block_kv": bkv}
        fns = kernels(window, ordered, bq, bkv)
        if not args.compile:
            # a real forward's out / lse, so that the backward's
            # probabilities are probabilities
            out, lse = jax.jit(fns["flash_fwd"])(*(ops[n] for n in order))
            ops["out"], ops["lse"] = out, lse
        for name, fn in fns.items():
            jitted = jax.jit(fn)
            operands = [ops[n] for n in order]
            try:
                if args.compile:
                    jitted.lower(*operands).compile()
                    row[name] = "compiles"
                    continue
                res = jax.block_until_ready(jitted(*operands))
                if not ordered:
                    full_grid[window, name] = res
                else:
                    # 0.0 at the full grid's blocks: the same bits
                    row[name + "_gap"] = max(
                        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                              - b.astype(jnp.float32))))
                        for a, b in zip(jax.tree.leaves(res), jax.tree.leaves(
                            full_grid[window, name])))
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    res = jitted(*operands)
                jax.block_until_ready(res)
                row[name + "_ms"] = round(
                    (time.perf_counter() - t0) / args.iters * 1e3, 3)
            except Exception as e:  # noqa: BLE001 - the table's point
                row[name] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(row), flush=True)
        rows.append(row)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "compile_only": args.compile, "shape": [B, H, S, DH],
                   "kv_heads": K, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
