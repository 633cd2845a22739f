"""Per-kernel compile table on the attached TPU.

Runs every registered kernel case (``ops/registry.py``) whose mesh the
attached devices can form — values and grads, compiled (``interpret=
None`` resolves to Mosaic on a TPU) — against its oracle and the pinned
tolerance ledger, then each fused kernel once at Llama-3.1-8B widths
(d_model 4096, vocab 128256, rows 2x1024), then the ``KERNELCHECK=1``
startup probe. One line per case: ``ok`` with the observed error and
the pin, ``outside-ledger`` (compiled and ran, error beyond the pin) or
``refused`` with the compiler's message. The ledger was pinned on the
CPU interpreter under ``jax_default_matmul_precision=float32``
(tests/conftest.py), so the float32 cases of the registry sweep run
under that setting (at the TPU's default precision a float32 dot is a
single bf16 pass, in the oracle as much as in the kernel). The bf16
cases, the width runs and the startup probe run at the default
precision, as training does — Mosaic refuses a bf16 matmul that asks
for fp32 contract precision. Nothing here is a time or a rate.

    python scripts/chip_kernels.py        # through the chip tool
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def first_line(e: BaseException) -> str:
    lines = [ln.strip() for ln in str(e).splitlines() if ln.strip()]
    return f"{type(e).__name__}: {' | '.join(lines[:3])}"[:600]


def registry_rows() -> list:
    from gke_ray_train_tpu.analysis import kernelcheck as kc
    from gke_ray_train_tpu.ops import registry
    rows = []
    for spec in registry.all_kernels():
        for case in spec.cases:
            n = 1
            for v in (case.mesh_axes or {}).values():
                n *= v
            row = {"kernel": spec.name, "case": case.name}
            if case.mesh_axes is not None and n != len(jax.devices()):
                row.update(status="not-run",
                           detail=f"needs a {n}-device mesh")
                rows.append(row)
                continue
            precision = "float32" if case.dtype == "float32" else None
            try:
                with jax.default_matmul_precision(precision):
                    res = kc.run_case(spec, case)
            except Exception as e:  # noqa: BLE001 - the table's point
                row.update(status="refused", detail=first_line(e))
                rows.append(row)
                continue
            row.update(observed=res.metrics(), pinned=(
                kc.load_ledger(spec.name) or {}).get("cases", {}).get(
                    case.name))
            bad = [f for f in kc.ledger_findings([res])
                   if f.rule != "KER102"]
            row["status"] = "outside-ledger" if bad else "ok"
            rows.append(row)
    return rows


def width_rows() -> list:
    """Each fused kernel once at the smoke model's widths, value and
    grad against the unfused XLA path (no ledger pin at this size)."""
    from gke_ray_train_tpu.analysis.kernelcheck import _tree_err
    from gke_ray_train_tpu.ops.fused_ce import fused_cross_entropy
    from gke_ray_train_tpu.ops.fused_norm_rope import (
        fused_rmsnorm, fused_rope_qk)
    from gke_ray_train_tpu.ops.norms import rms_norm
    from gke_ray_train_tpu.ops.rope import apply_rope, rope_frequencies
    from gke_ray_train_tpu.train.step import token_nll
    B, S, D, V, H, K, dh = 2, 1024, 4096, 128256, 32, 8, 128
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 8)
    x = jax.random.normal(ks[0], (B, S, D), jnp.float32).astype(bf)
    scale = (jax.random.normal(ks[1], (D,)) * 0.1 + 1.0).astype(bf)
    q = jax.random.normal(ks[2], (B, S, H, dh), jnp.float32).astype(bf)
    k = jax.random.normal(ks[3], (B, S, K, dh), jnp.float32).astype(bf)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    freqs = jnp.asarray(rope_frequencies(dh, theta=500000.0))
    head = (jax.random.normal(ks[4], (D, V), jnp.float32) * 0.02).astype(bf)
    tgt = jax.random.randint(ks[5], (B, S), 0, V, jnp.int32)
    w = (jax.random.uniform(ks[6], (B, S)) > 0.2).astype(jnp.float32)

    def s32(tree):
        return sum(jnp.sum(t.astype(jnp.float32))
                   for t in jax.tree.leaves(tree))

    def ce_ref(x, head):
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return token_nll(logits, tgt, w)[0]

    pairs = [
        ("fused_rmsnorm", f"x[{B},{S},{D}] bf16",
         lambda x, s: fused_rmsnorm(x, s), lambda x, s: rms_norm(x, s),
         (x, scale)),
        ("fused_rope_qk", f"q[{B},{S},{H},{dh}] k[{B},{S},{K},{dh}] bf16",
         lambda q, k: fused_rope_qk(q, k, pos, freqs),
         lambda q, k: (apply_rope(q, pos, freqs), apply_rope(k, pos, freqs)),
         (q, k)),
        ("fused_cross_entropy", f"x[{B},{S},{D}] head[{D},{V}] bf16",
         lambda x, h: fused_cross_entropy(x, h, tgt, w)[0], ce_ref,
         (x, head)),
    ]
    rows = []
    for name, shape, kern, ref, args in pairs:
        for what, fk, fr in (
                ("value", jax.jit(kern), jax.jit(ref)),
                ("grad", jax.jit(jax.grad(lambda *a: s32(kern(*a)),
                                          argnums=(0, 1))),
                 jax.jit(jax.grad(lambda *a: s32(ref(*a)),
                                  argnums=(0, 1))))):
            row = {"kernel": name, "case": f"{shape} {what}"}
            try:
                row.update(status="ran", observed={
                    "rel_err_vs_unfused": _tree_err(fk(*args),
                                                    fr(*args))})
            except Exception as e:  # noqa: BLE001 - the table's point
                row.update(status="refused", detail=first_line(e))
            rows.append(row)
    return rows


def main() -> int:
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d)}
    print("chip_kernels:", device, flush=True)
    if device["platform"] != "tpu":
        print("chip_kernels: FAILED: no TPU attached", file=sys.stderr)
        return 1
    rows = registry_rows() + width_rows()
    from gke_ray_train_tpu.analysis.kernelcheck import (
        KernelCheckError, quick_verify)
    try:
        probe = {"status": "ok", "verified": len(quick_verify())}
    except KernelCheckError as e:
        probe = {"status": "failed", "detail": str(e)[:2000]}
    except Exception as e:  # noqa: BLE001 - a compiler refusal
        probe = {"status": "refused", "detail": first_line(e)}
    for r in rows:
        print(f"{r['status']:15s} {r['kernel']}/{r['case']}  "
              f"{r.get('observed', '')} pinned={r.get('pinned', '-')} "
              f"{r.get('detail', '')}", flush=True)
    print("KERNELCHECK=1 probe:", probe, flush=True)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chip_kernels.json"), "w") as f:
        json.dump({"device": device, "rows": rows, "kernelcheck": probe},
                  f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
