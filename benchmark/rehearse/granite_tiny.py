"""Rehearsal of the hybrid state-space / attention routed decoder's cell
at tiny widths on the CPU (run by hand before chip time is spent):

    JAX_PLATFORMS=cpu python benchmark/rehearse/granite_tiny.py

``rehearse/tiny.py``'s ``shrink`` knows a dense decoder's keys; this one
shrinks the mixer's and the routed layer's too and keeps the published
structure: one period of the layer pattern cut to four layers (three
Mamba-2 mixers around one attention layer: 8 heads of 32, a state of
64, chunks of 16, so that documents begin inside chunks; hidden 128),
16 router outputs of which 4 experts are held, 3 a token, a shared
expert of its own width, the four multipliers as published, rows packed
from several documents. It prints each result line (both trace modes);
the numbers are rehearsal output and mean nothing about the chip.

The rehearsal is judged under limits of its own (:data:`LIMITS`), not
the cell's: it computes in float32, where a sound run reads under 1e-5
on every compared number, and at hidden 128 with weights of std 0.02
the state is a thousandth of the mixer's output, so a state carried
across a document boundary moves the first gradient by 6e-4: far above
float32's rounding, far under limits that are set for bfloat16 at the
published widths (where the state is of the skip's own size).
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("COMPILE_CACHE", "0")

CELL = "granite4hsmall_ep4_l20.qlora_sft_packed_8k_ssm"
TINY_CONFIG = {
    "hidden_size": 128, "intermediate_size": 64,
    "shared_intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.03125,
    "mamba_n_heads": 8, "mamba_d_head": 32, "mamba_d_state": 64,
    "mamba_chunk_size": 16,
    "num_hidden_layers": 4, "num_hidden_layers_published": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "vocab_size": 512, "max_position_embeddings": 256,
    "num_local_experts": 4, "experts_held": [4, 8], "router_outputs": 16,
    "num_experts_per_tok": 3,
}


# float32 at four layers: 10 x and more above what a sound run reads
# (grad_gap, grad_dir_gap, ssm_dir_gap under 1e-6, change_gap 7e-6)
LIMITS = {"grad_gap": 1e-4, "change_gap": 1e-4, "grad_dir_gap": 1e-4,
          "ssm_dir_gap": 1e-4, "pairs_gap": 0.004}


def shrink(files: dict, dtype: str = "float32") -> None:
    files["config"].update(TINY_CONFIG)
    files["limits"] = dict(files["limits"], limits=dict(LIMITS))
    mix = files["mix"]
    mix["job"].update(MAX_SEQ_LENGTH=128, LORA_R=8, TRAIN_DTYPE=dtype,
                      NUM_TRAIN_SAMPLES=256, AOT_TRAIN_STEP=False)
    mix["rows"].update(count=256, docs_per_row=4, length={
        "dist": "lognormal", "median": 24, "sigma": 0.6,
        "min": 8, "max": 64})


def main() -> int:
    from benchmark import harness as hs
    from benchmark import run
    bad = 0
    for trace in (False, True):
        out = run.run_cell(CELL, seed=2**31 + 12345, seconds=3.0,
                           trace=trace, require_chip=False,
                           t_start=time.perf_counter(), override=shrink)
        hs.emit(out["result"], out["checks"], out["notes"])
        bad += not out["result"]["correct"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
