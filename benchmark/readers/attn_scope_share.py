"""Share of the traced slice's device-operation time spent under the
attention's scopes named in ``stages`` (``attn/<stage>``: ``q_latent``,
``kv_latent``, ``rope``, ``core``, ``out``, ``qkv``), all phases. A
latent layer's projections are ``q_latent`` + ``kv_latent`` (each
latent's down-projection, norm and up-projection); its kernels run under
``core``. A program without such a scope gives nothing to read."""

import re

from benchmark.readers import program_trace as pt


def read(facts, stages):
    ops = pt.attributed(facts)
    if not ops:
        return None
    pattern = re.compile(r"(^|/)attn/(" + "|".join(stages) + r")(/|$)")
    if not any(path and pattern.search(path) for _, _, _, path in ops):
        return None
    return pt.share(ops, lambda _op, path: bool(
        path and pattern.search(path)))
