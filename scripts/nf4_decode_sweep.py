"""NF4 decode sweep on the attached TPU (ROADMAP S3 / S9).

Times the decode of a quantised leaf, jitted with the compile options
every step of the program is built with (``plan.py::XLA_TPU_OPTIONS``
and the overlap flags), at the shapes the benchmark's three cells run
(read off ``benchmark/configs/*.json`` and the traffic mixes' rows a
micro-pass) and at one serving decode's (32 rows):

- ``alone``: the decode into bf16, written to HBM (what a routed layer
  does to a bank before ``gmm``);
- ``fwd`` / ``dx``: the decode and the product that consumes it,
  ``x @ W`` and ``g @ W.T`` (what ``_proj`` runs forward, and its
  backward for the activation's cotangent);
- ``bf16``: the same product over a weight that is bf16 already, the
  MXU's measured floor beside the computed one (2 m k n / 197e12).

A candidate is a lookup and a form. ``chain`` (fifteen compares and
selects, PR 30 and before) and ``tree`` (``ops/quant.py::_nf4_lookup``
since PR 31) return ``NF4_CODEBOOK[code]`` as float32 bit for bit, and
the sweep compares every such candidate's outputs with the chain's on
the chip; ``convert`` is the control and no decode: the code itself as
a float, which sizes what a lookup pays around it. Plain names run the
lookup inside the ``dequantize`` of PR 30 and before, where XLA moves
the group reshape onto the scales' broadcast and writes it to HBM;
``grouped.*`` hand the fusion its codes in the grouped shape behind an
optimization barrier, which is what ``ops/quant.py::dequantize`` does
since PR 31; ``shipped`` is that function itself. ``int8`` /
``int8.shipped`` are the same two forms over an int8 leaf. The anatomy
of the control: ``noscale`` (codes to bf16 and nothing else) and
``colscale`` (one scale a column, no group) say what the group's scale
costs. Every candidate runs at the dense gate/up shape, where the
choice was made; ``chain`` and ``shipped`` run everywhere. The nine
lookups PR 31 tried and left (narrow masks, a 4 x 4 tree, ``select_n``,
scaled tables) are in PERF.md section 6's table, not here. Not a cell:
nothing here is an end-to-end number; a run is 256 s of command on one
chip (70 programs, most of it their compiles).

    python scripts/nf4_decode_sweep.py             # through the chip tool
    python scripts/nf4_decode_sweep.py --compile   # no chip: compile for a
                                                   # described v5e, count
                                                   # the fusion's operations
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gke_ray_train_tpu.ops import quant  # noqa: E402
from gke_ray_train_tpu.ops.quant import NF4_CODEBOOK, QTensor  # noqa: E402

PEAK_FLOPS = 197e12   # benchmark/peaks.json, "TPU v5 lite", bf16
BOOK = [np.float32(v) for v in NF4_CODEBOOK]


def chain(codes):
    """The decode up to PR 30: fifteen compares, fifteen selects."""
    c = codes.astype(jnp.int32)
    out = jnp.full(c.shape, BOOK[0], jnp.float32)
    for i in range(1, 16):
        out = jnp.where(c == i, BOOK[i], out)
    return out


def tree(codes):
    """A select tree on the code's four bits: 8 selects between pairs of
    constants by bit 0, then 4, 2, 1."""
    c = codes.astype(jnp.int32)
    level = BOOK
    for m in [(c & (1 << b)) != 0 for b in range(3)] + [c >= 8]:
        level = [jnp.where(m, hi, lo)
                 for lo, hi in zip(level[::2], level[1::2])]
    return level[0]


def convert(codes):
    """Control, NOT a decode: the code as a float."""
    return codes.astype(jnp.float32)


def decode(qt, dtype, lookup, grouped):
    """``dequantize`` with ``lookup`` for the codebook; ``grouped``: the
    codes reach the fusion in the grouped shape (PR 31), else as PR 30
    had it."""
    *lead, D, F = qt.codes.shape
    g = qt.group
    codes = qt.codes.reshape(*lead, D // g, g, F)
    if grouped:
        codes = jax.lax.optimization_barrier(codes)
    scaled = lookup(codes) * qt.scales[..., :, None, :]
    return scaled.reshape(*lead, D, F).astype(dtype)


LOOKUPS = {"chain": chain, "tree": tree, "convert": convert}
# name -> dequantize(qt, dtype)
CANDIDATES = {
    **{name: functools.partial(decode, lookup=fn, grouped=False)
       for name, fn in LOOKUPS.items()},
    **{"grouped." + name: functools.partial(decode, lookup=fn, grouped=True)
       for name, fn in LOOKUPS.items()},
    "shipped": quant.dequantize,
    # over an int8 leaf of the same shape: PR 30's form (the control's
    # arithmetic) and the shipped one
    "int8": functools.partial(decode, lookup=convert, grouped=False),
    "int8.shipped": quant.dequantize,
    # the anatomy of the control: no scale at all; a scale a column
    "noscale": lambda qt, dtype: qt.codes.astype(jnp.float32).astype(dtype),
    "colscale": lambda qt, dtype: (
        qt.codes.astype(jnp.float32) * qt.scales[..., :1, :]).astype(dtype),
}
ELSEWHERE = ["chain", "shipped"]
SERVING = ELSEWHERE + ["int8", "int8.shipped"]
INEXACT = ("convert", "grouped.convert", "int8", "int8.shipped", "noscale",
           "colscale")


def cell_shapes():
    """(name, rows a micro-pass, [D, F] or a bank's [E, D, F], modes,
    candidates; None: ``--elsewhere``)."""
    def cfg(name):
        with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
            return json.load(f)
    dense, routed, latent = (cfg("mistral7b.json"),
                             cfg("kexaone236b_ep8_l8.json"),
                             cfg("glm47flash_ep4.json"))
    d, ff = dense["hidden_size"], dense["intermediate_size"]
    lh, heads = latent["hidden_size"], latent["num_attention_heads"]
    fused = ("alone", "fwd", "dx")
    return [
        # 2 rows x 1024 a micro-pass
        ("dense.gate_up", 2048, (d, ff), fused, list(CANDIDATES)),
        ("dense.down", 2048, (ff, d), fused, None),
        # banks of 16 held experts, decoded whole before gmm
        ("routed.bank", 0, (routed["num_experts"], routed["hidden_size"],
                            routed["moe_intermediate_size"]), ("alone",),
         None),
        ("latent.bank", 0, (latent["n_routed_experts"], lh,
                            latent["moe_intermediate_size"]), ("alone",),
         None),
        # 1 packed row of 8192 a micro-pass
        ("latent.shared_gate", 8192,
         (lh, latent["moe_intermediate_size"]), fused, None),
        ("latent.wo", 8192, (heads * latent["v_head_dim"], lh), fused, None),
        # the serving engine's decode step: 32 sequences, a token each
        ("serve.gate_up", 32, (d, ff), ("fwd",), SERVING),
    ]


def compile_options():
    from gke_ray_train_tpu.plan import XLA_OVERLAP_OPTIONS, XLA_TPU_OPTIONS
    return {**XLA_TPU_OPTIONS, **XLA_OVERLAP_OPTIONS}


def programs(mode, deq):
    bf16 = jnp.bfloat16
    return {
        "alone": lambda x, qt: deq(qt, bf16),
        "fwd": lambda x, qt: jnp.einsum("md,dh->mh", x, deq(qt, bf16)),
        "dx": lambda x, qt: jnp.einsum("mh,dh->md", x, deq(qt, bf16)),
    }[mode]


def fusion_ops(hlo: str):
    """Elementwise operations of the optimised HLO by opcode: what the
    compiler kept of a candidate."""
    count = collections.Counter(re.findall(
        r"= \S+ (compare|select|and|or|xor|shift-left|"
        r"shift-right-logical|shift-right-arithmetic|convert|multiply|"
        r"bitcast-convert|fusion|convolution)\(", hlo))
    return dict(sorted(count.items()))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--elsewhere", type=json.loads, default=ELSEWHERE,
                    help="JSON list of names, at the shapes that name none")
    ap.add_argument("--shapes", type=json.loads, default=None,
                    help="JSON list of shape names; default all")
    ap.add_argument("--out", default="chiprun_out/nf4_decode_sweep.json")
    args = ap.parse_args()

    if args.compile:
        os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        sh = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1)).devices[0])

        def array(key, shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
    else:
        def array(key, shape, dtype):
            if dtype in (jnp.uint4, jnp.int8):
                return jax.random.randint(
                    key, shape, 0, 16, jnp.int32).astype(dtype)
            if dtype == jnp.float32:    # scales: absmax of a group
                return jax.random.uniform(key, shape, dtype, 0.03, 0.09)
            return jax.random.normal(key, shape, dtype)
    opts = compile_options()
    keys = jax.random.split(jax.random.key(31), 4)

    rows = []
    for name, m, wshape, modes, cands in cell_shapes():
        if args.shapes and name not in args.shapes:
            continue
        cands = cands or args.elsewhere
        *lead, D, F = wshape
        weights = int(np.prod(wshape))
        group = quant.DEFAULT_GROUP
        qt = QTensor(array(keys[0], wshape, jnp.uint4),
                     array(keys[1], (*lead, D // group, F), jnp.float32),
                     "nf4", group)
        qt8 = QTensor(array(keys[0], wshape, jnp.int8), qt.scales, "int8",
                      group) if any(c.startswith("int8") for c in cands) \
            else None
        for mode in modes:
            x = None if mode == "alone" else array(
                keys[2], (m, F if mode == "dx" else D), jnp.bfloat16)
            floor_ms = None if mode == "alone" else (
                2 * m * D * F / PEAK_FLOPS * 1e3)
            base = {"shape": name, "weight": list(wshape), "rows": m,
                    "mode": mode, "weights": weights,
                    "mxu_floor_ms": floor_ms}

            def timed(fn, *operands):
                res = jax.block_until_ready(fn(*operands))
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = fn(*operands)
                jax.block_until_ready(out)
                return res, (time.perf_counter() - t0) / args.iters * 1e3

            bf16_ms = None
            if mode != "alone":
                # the product over a weight that is bf16 already
                spec = "md,dh->mh" if mode == "fwd" else "mh,dh->md"
                plain = jax.jit(lambda x, w, s=spec: jnp.einsum(s, x, w),
                                compiler_options=opts)
                w = array(keys[3], wshape, jnp.bfloat16)
                if args.compile:
                    plain.lower(x, w).compile()
                else:
                    _, bf16_ms = timed(plain, x, w)
                    del w
                    rows.append({**base, "candidate": "bf16",
                                 "ms": round(bf16_ms, 4)})
                    print(json.dumps(rows[-1]), flush=True)
            want = None
            for cand in cands:
                row = {**base, "candidate": cand}
                leaf = qt8 if cand.startswith("int8") else qt
                fn = jax.jit(programs(mode, CANDIDATES[cand]),
                             compiler_options=opts)
                try:
                    if args.compile:
                        built = fn.lower(x, leaf).compile()
                        row["ops"] = fusion_ops(built.as_text())
                        # a broadcast of the scales that went to HBM
                        # shows here: 4 bytes a weight
                        row["temp_bytes"] = (
                            built.memory_analysis().temp_size_in_bytes)
                    else:
                        res, ms = timed(fn, x, leaf)
                        row["ms"] = round(ms, 4)
                        row["ps_a_weight"] = round(ms * 1e9 / weights, 3)
                        if bf16_ms is not None:
                            row["ps_over_bf16"] = round(
                                (ms - bf16_ms) * 1e9 / weights, 3)
                            row["of_mxu_floor"] = round(floor_ms / ms, 4)
                        if cand not in INEXACT:
                            if want is None:
                                want = res
                            row["same_bits"] = bool(jnp.array_equal(
                                res, want))
                        del res
                except Exception as e:  # noqa: BLE001 - the table's point
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                print(json.dumps(row), flush=True)
                rows.append(row)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "compile_only": args.compile, "iters": args.iters,
                   "compiler_options": opts, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
