"""Batched multi-LoRA matmul — the multi-tenant decode primitive.

Punica/S-LoRA-style BGMV ("batched gather matrix-vector"): a mixed-tenant
decode batch carries a per-row adapter slot index ``aslot`` [B] into one
shared executable; every row's activations go through *its own* tenant's
low-rank A/B pair, selected from a stacked adapter pool, without any
per-tenant dispatch or recompile.

Layout contract (mirrors ``train/lora.py`` single-adapter trees):

- a single adapter leaf is ``[n_repeats, d_in, r]`` (A) /
  ``[n_repeats, r, d_out]`` (B), one dict per block-pattern position;
- the pool stacks adapters at **axis 1** — ``[n_repeats, A, d_in, r]`` —
  so the scanned-block axis stays leading and a per-repeat ``lax.scan``
  slice is ``[A, d_in, r]`` with the adapter axis leading (the layout
  ``ops/registry.py``'s ``lora_batched`` kernel spec pins);
- ``gather_pool`` selects per-row adapters from one repeat's slice
  INSIDE the block scan (``[A, d_in, r]`` → ``[B, d_in, r]``), so
  ``_proj`` sees a 3-D per-row entry and runs ``bgmv``. Gathering all
  layers at once outside the scan would hold ``B`` full adapter copies
  live for the whole forward — 8 rows of an r=64 Llama-3.1-8B adapter
  do not fit one 16 GB chip beside the base weights.

Reference path is pure einsum — exact on the CPU mesh, and the oracle
ledger (tests/tolerances/lora_batched.json) pins it at 0.0 against the
per-request sequential single-adapter loop. A Pallas grouped-GEMM
variant (segment the batch by slot, one MXU tile per group) is the
natural TPU follow-up; the einsum path is the semantics contract it
would be ledger-pinned against.

Serving is forward-only, so the registry spec is value-only
(``grads=False``) — there is no backward contract to pin.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def gather_pool(pool_slice: Any, aslot: jnp.ndarray) -> Any:
    """Select each batch row's adapter from one repeat's pool slice.

    ``pool_slice``: pytree of ``[A, ...]`` leaves (the per-repeat scan
    slice of the stacked pool, adapter axis leading); ``aslot``: ``[B]``
    int32 slot indices. Returns the same tree with leaves ``[B, ...]`` —
    row ``b`` carries adapter ``aslot[b]``.
    """
    return jax.tree.map(lambda p: jnp.take(p, aslot, axis=0), pool_slice)


def bgmv(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, *,
         scale: float, dtype: jnp.dtype) -> jnp.ndarray:
    """Per-row low-rank bypass: row ``i`` of ``x`` [B, T, d_in] through
    its own ``a[i]`` [B, d_in, r] / ``b[i]`` [B, r, d_out] pair →
    [B, T, d_out] delta, scaled like the single-adapter ``_proj`` path.

    Identical contraction order and dtype discipline as transformer
    ``_proj``'s 2-D branch (x·A in ``dtype``, then ·B, then *scale) so a
    batch where every row selects the same slot is bitwise the
    single-adapter result.
    """
    xa = jnp.einsum("btd,bdr->btr", x, a.astype(dtype))
    return jnp.einsum("btr,brh->bth", xa, b.astype(dtype)) \
        * jnp.asarray(scale, dtype)


def lora_batched_matmul(x: jnp.ndarray, a_pool: jnp.ndarray,
                        b_pool: jnp.ndarray, aslot: jnp.ndarray, *,
                        scale: float = 1.0,
                        dtype: Any = jnp.float32) -> jnp.ndarray:
    """gather + bgmv for ONE projection — the registry-facing op.

    ``a_pool`` [A, d_in, r] / ``b_pool`` [A, r, d_out] with the adapter
    axis leading (a per-repeat slice of the stacked pool), ``x``
    [B, T, d_in], ``aslot`` [B] → [B, T, d_out].
    """
    dt = jnp.dtype(dtype)
    a = jnp.take(a_pool, aslot, axis=0)
    b = jnp.take(b_pool, aslot, axis=0)
    return bgmv(x.astype(dt), a, b, scale=scale, dtype=dt)
