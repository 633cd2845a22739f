"""The dense decoder's seam to the program (its model configuration from
the published key names, the benchmark's seeded weights laid out as the
program stores them, quantised by the program's own quantiser, one
jitted call), and what every training family's run reads its leaves,
gradients and trace slice with.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as wts


def model_config(config: dict, *, dtype: str, param_dtype: str,
                 attn_impl: str = "auto", remat_policy: str = "full"):
    from gke_ray_train_tpu.models.config import ModelConfig
    window = config.get("sliding_window")
    max_pos = int(config["max_position_embeddings"])
    return ModelConfig(
        name=str(config.get("model_type", "model")),
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        head_dim=config.get("head_dim"),
        max_seq_len=min(max_pos, int(window)) if window else max_pos,
        norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        block_pattern=("sliding",) if window else ("global",),
        sliding_window=int(window) if window else None,
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        dtype=dtype, param_dtype=param_dtype, attn_impl=attn_impl,
        remat_policy=remat_policy)


def params_maker(cfg, config: dict, *, quant_kind: Optional[str],
                 quant_group: int = 64):
    """``make(key) -> tree`` in the program's layout and the types it is
    run in: projections quantised slice by slice as they are drawn
    (never a full-precision tree first), the rest in
    ``cfg.param_dtype``."""
    from gke_ray_train_tpu.ops.quant import QTensor, quantize_tensor

    dims = wts.dims_from_config(config)
    pat, reps = len(cfg.block_pattern), cfg.n_repeats
    pdt = jnp.dtype(cfg.param_dtype)
    quant = quant_kind not in (None, "none")

    def stack(key, name, p, as_codes):
        def one(r):
            layer = r * pat + p
            if not as_codes:
                return wts.stored(dims, key, name, layer, pdt)
            w = wts.stored(dims, key, name, layer, jnp.bfloat16)
            qt = quantize_tensor(w[None], quant_kind, quant_group)
            return qt.codes[0], qt.scales[0]
        return jax.lax.map(one, jnp.arange(reps, dtype=jnp.int32))

    def make(key):
        blocks = []
        for p in range(pat):
            b = {n: stack(key, n, p, False)
                 for n in ("attn_norm", "mlp_norm")}
            for n in wts.PROJECTIONS:
                if quant:
                    codes, scales = stack(key, n, p, True)
                    b[n] = QTensor(codes, scales, quant_kind, quant_group)
                else:
                    b[n] = stack(key, n, p, False)
            blocks.append(b)
        return {"embed": wts.stored(dims, key, "embed", 0, pdt),
                "blocks": blocks,
                "final_norm": wts.stored(dims, key, "final_norm", 0, pdt),
                "lm_head": wts.stored(dims, key, "lm_head", 0, pdt)}
    return make


def param_shardings(cfg, abstract, mesh):
    from gke_ray_train_tpu.models.transformer import param_specs
    from gke_ray_train_tpu.ops.quant import quant_specs
    from gke_ray_train_tpu.parallel.sharding import tree_shardings
    return tree_shardings(
        mesh, quant_specs(param_specs(cfg), abstract, mesh))


def build_params(cfg, config: dict, seed: int, mesh, *,
                 quant_kind: Optional[str], quant_group: int = 64):
    """The whole tree in one jitted call from the seed."""
    make = params_maker(cfg, config, quant_kind=quant_kind,
                        quant_group=quant_group)
    key = wts.seed_key(seed)
    shardings = param_shardings(cfg, jax.eval_shape(make, key), mesh)
    return jax.jit(make, out_shardings=shardings)(key)


def build_lora(cfg, config: dict, seed: int, mesh, lora_cfg):
    """LoRA adapters in the program's layout: A from the seed, B zero."""
    from gke_ray_train_tpu.parallel.sharding import tree_shardings
    from gke_ray_train_tpu.train.lora import lora_specs

    dims = wts.dims_from_config(config)
    pat, reps = len(cfg.block_pattern), cfg.n_repeats

    def make(key):
        def block(p):
            def a_of(t):
                return jax.lax.map(
                    lambda r: wts.lora_a(dims, key, t, r * pat + p,
                                         lora_cfg.r),
                    jnp.arange(reps, dtype=jnp.int32))
            return {t: {"a": a_of(t),
                        "b": jnp.zeros((reps,) + wts.lora_b_shape(
                            dims, t, lora_cfg.r), jnp.float32)}
                    for t in lora_cfg.targets}
        return {"blocks": [block(p) for p in range(pat)]}

    shardings = tree_shardings(mesh, lora_specs(cfg, lora_cfg))
    return jax.jit(make, out_shardings=shardings)(wts.seed_key(seed))


def named_leaves(tree) -> dict:
    """{"wq.a": leaf, ...}: the path's string keys, without the list
    indices of the block pattern (a leaf spans all its layers)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(e.key) for e in path if hasattr(e, "key")]
        idx = [str(e.idx) for e in path if hasattr(e, "idx")]
        name = ".".join(k for k in keys if k != "blocks")
        if idx and idx != ["0"]:
            name += "@" + ".".join(idx)
        out[name] = leaf
    return out


def by_leaf_name(norms: Dict[str, float]) -> Dict[str, float]:
    """{"wq.a": norm over all layers}: ``named_leaves`` gives one entry
    a block dict of the program's tree (``prologue.wq.a@1``), the
    reference one a leaf name over all its layers."""
    out: Dict[str, float] = {}
    for name, v in norms.items():
        base = name.split("@")[0].removeprefix("prologue.")
        out[base] = out.get(base, 0.0) + v * v
    return {k: float(np.sqrt(v)) for k, v in out.items()}


def pairs_gap(pairs, reference_pairs) -> float:
    """Held pairs counted over the followed steps against the
    reference's count."""
    return abs(sum(pairs) - sum(reference_pairs)) \
        / max(sum(reference_pairs), 1)


# ---------------------------------------------------------------------------
# the first gradient, tensor against tensor
# ---------------------------------------------------------------------------

def gradient_by_layer(cfg, tree, scale: float = 1.0):
    """A tree of the adapters' layout (stacked over a block's layers) ->
    one ``{target: {"a", "b"}}`` a layer, as the reference holds its
    gradient, in float32 on the host."""
    from gke_ray_train_tpu.models.transformer import block_layout
    out = [None] * cfg.n_layers
    for where, i, first, count, stride, _ in block_layout(cfg):
        for r in range(count):
            out[first + r * stride] = jax.tree.map(
                lambda x, r=r: np.asarray(x[r], np.float32) * scale,
                tree[where][i])
    return out


def gradient_table(got, want) -> Dict[str, np.ndarray]:
    """{"wq_a.b": [layers, 3]}: a layer's |got|^2, |want|^2 and
    got . want of that leaf (noughts where a layer has no such leaf).
    Every number that sets two gradients tensor against tensor comes
    from these three."""
    out: Dict[str, np.ndarray] = {}
    for layer, (g, w) in enumerate(zip(got, want)):
        for t, ab in w.items():
            for k, ref in ab.items():
                mine = np.asarray(g[t][k], np.float64).ravel()
                ref = np.asarray(ref, np.float64).ravel()
                row = out.setdefault(f"{t}.{k}", np.zeros((len(want), 3)))
                row[layer] = mine @ mine, ref @ ref, mine @ ref
    return out


def direction_gap(table: Dict[str, np.ndarray], targets=None) -> float:
    """Largest |got - want| / |want| over the leaves (of ``targets``,
    or all), each leaf one vector over all its layers; a leaf whose
    reference gradient is under the median leaf's is measured against
    the median, as ``check.worst_leaf_gap`` does. A gap of norms is
    second order in random rounding and nearly cancels; this one is
    first order and does not cancel, so it rises with every rounding on
    the way."""
    sums = {k: t.sum(0) for k, t in table.items()}
    floor = float(np.median([s[1] for s in sums.values()]))
    return max(float(np.sqrt(max(s[0] - 2 * s[2] + s[1], 0.0)
                             / max(s[1], floor, 1e-300)))
               for k, s in sums.items()
               if targets is None or k.split(".")[0] in targets)


class TraceSlice:
    """Takes the place of the loop's profiler. The trace opens at the
    first step boundary once ``start_after`` seconds of the window have
    passed (so that it covers the window's last part) and is written
    out by ``finish``, which the driver calls once the window's end has
    been stamped: writing a trace stalls the host, and that stall
    belongs to no step."""

    def __init__(self, out_dir: str, start_after: float):
        self.out_dir, self.start_after = out_dir, start_after
        self.active = False
        self.t_window0 = time.perf_counter()
        self.t0 = self.t1 = None

    def start(self):
        import jax
        # the program's spans and XLA's host events, no Python tracer:
        # it would slow the very host loop whose gaps are being read
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.t0 = time.perf_counter()
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.active = True

    def step(self, global_step=None):
        if self.t0 is None and \
                time.perf_counter() - self.t_window0 >= self.start_after:
            self.start()

    def close(self):
        """The traced slice ends here (the loop calls this on its way
        out); the trace itself is written by ``finish``."""
        if self.active and self.t1 is None:
            self.t1 = time.perf_counter()

    def finish(self):
        if self.active:
            import jax
            self.close()
            jax.profiler.stop_trace()
            self.active = False
