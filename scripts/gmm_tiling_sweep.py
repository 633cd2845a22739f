"""Grouped-matmul tiling sweep on the attached TPU.

Times the megablox ``gmm`` kernel alone at the shapes of one routed
layer-pass: a pair buffer of ``--rows`` rows of which ``--live`` are
real, ``--groups`` experts, one product ``[rows, k] x [groups, k, n]``
and the product its backward makes for dx (the same kernel over the
transposed bank: contraction ``n``, output ``k``). The default is the
GLM cell's expert (2048 x 1536 and 1536 x 2048, 16 experts, 32,768 rows,
~8,200 live); ``--k 6144 --n 2048 --rows 65536`` is the routed cell's.
``ops/moe.py::gmm_tiling`` holds the rule read off this table (PERF.md
section 6). Not a cell: nothing here is an end-to-end number.

    python scripts/gmm_tiling_sweep.py             # through the chip tool
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=32768)
    ap.add_argument("--live", type=int, default=8200)
    ap.add_argument("--groups", type=int, default=16)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--n", type=int, default=1536)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tm", type=json.loads, default=[256, 512])
    ap.add_argument("--tk", type=json.loads, default=[512, 768, 1024, 2048])
    ap.add_argument("--tn", type=json.loads, default=[512, 768, 1024, 2048])
    ap.add_argument("--out", default="chiprun_out/gmm_tiling_sweep.json")
    args = ap.parse_args()
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from gke_ray_train_tpu.ops.moe import gmm_tiling

    rng = np.random.default_rng(30)
    # an uneven split of the live rows over the groups, as a router's
    cuts = np.sort(rng.integers(0, args.live, args.groups - 1))
    sizes = jnp.asarray(np.diff(np.concatenate([[0], cuts, [args.live]])),
                        jnp.int32)
    keys = jax.random.split(jax.random.key(30), 4)
    rows = []
    # (name, contraction, output, transpose_rhs): the forward product and
    # the dx product of its backward, for both matrices of the expert
    products = [("k_to_n", args.k, args.n, False),
                ("n_to_k.T", args.n, args.k, True),
                ("n_to_k", args.n, args.k, False),
                ("k_to_n.T", args.k, args.n, True)]
    for name, c, o, transposed in products:
        x = jax.random.normal(keys[0], (args.rows, c), jnp.bfloat16)
        w = jax.random.normal(
            keys[1], (args.groups,) + ((o, c) if transposed else (c, o)),
            jnp.bfloat16) * 0.02
        rule = tuple(gmm_tiling(args.rows, c, o))
        seen = set()
        for tm, tk, tn in [rule] + list(itertools.product(
                args.tm, args.tk, args.tn)):
            tiling = (min(tm, args.rows), min(tk, c), min(tn, o))
            if tiling in seen:
                continue
            seen.add(tiling)
            row = {"product": name, "contraction": c, "output": o,
                   "tiling": list(tiling), "rule": tiling == rule}
            fn = jax.jit(lambda x, w, s, t=tiling, tr=transposed: gmm(
                x, w, s, preferred_element_type=jnp.bfloat16, tiling=t,
                transpose_rhs=tr))
            try:
                jax.block_until_ready(fn(x, w, sizes))
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    res = fn(x, w, sizes)
                jax.block_until_ready(res)
                row["ms"] = round(
                    (time.perf_counter() - t0) / args.iters * 1e3, 3)
            except Exception as e:  # noqa: BLE001 - the table's point
                row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(json.dumps(row), flush=True)
            rows.append(row)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "rows": args.rows, "live": args.live,
                   "groups": args.groups, "k": args.k, "n": args.n,
                   "sizes": [int(s) for s in sizes], "table": rows},
                  f, indent=1)


if __name__ == "__main__":
    main()
