"""Rehearsal of the latent-attention routed decoder's cell at tiny widths
on the CPU (run by hand before chip time is spent):

    JAX_PLATFORMS=cpu python benchmark/rehearse/glm_tiny.py

``rehearse/tiny.py``'s ``shrink`` knows a dense decoder's keys; this one
shrinks the latent layer's and the routed layer's too and keeps the
published structure: latent attention (4 heads of 24 values without
position + 8 rotated, values of 32, latents of 128 and 64: an NF4
group is 64 inputs), a leading
dense layer and three sparse ones, 16 router outputs of which 4 experts
are held, 2 a token, one shared expert, rows packed from several
documents. It prints each result line (both trace modes); the numbers
are rehearsal output and mean nothing about the chip.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("COMPILE_CACHE", "0")

CELL = "glm47flash_ep4.qlora_sft_packed_8k_mla"
TINY_CONFIG = {
    "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 128, "kv_lora_rank": 64, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 32,
    "num_hidden_layers": 4, "vocab_size": 512,
    "max_position_embeddings": 256,
    "moe_intermediate_size": 64, "n_routed_experts": 4,
    "experts_held": [4, 8], "router_outputs": 16,
    "num_experts_per_tok": 2,
}


def shrink(files: dict, dtype: str = "float32") -> None:
    files["config"].update(TINY_CONFIG)
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
