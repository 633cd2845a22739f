"""Rehearsal of the routed decoder's cell at tiny widths on the CPU
(run by hand before chip time is spent):

    JAX_PLATFORMS=cpu python benchmark/rehearse/exaone_tiny.py

``rehearse/tiny.py``'s ``shrink`` leaves the routed layer's own sizes as
published; this one shrinks those too, keeps a leading dense layer, two
periods of three windowed layers and a full one, 16 router outputs of
which 4 experts are held, and rows packed from several documents. It
prints each result line (both trace modes); the numbers are rehearsal
output and mean nothing about the chip.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("COMPILE_CACHE", "0")

CELL = "kexaone236b_ep8_l8.qlora_sft_packed_8k"
TINY_CONFIG = {
    "hidden_size": 128, "intermediate_size": 256, "head_dim": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 8, "num_hidden_layers_published": 8,
    "vocab_size": 512, "max_position_embeddings": 256, "sliding_window": 8,
    "moe_intermediate_size": 64, "num_experts": 4, "experts_held": [4, 8],
    "router_outputs": 16, "num_experts_per_tok": 4,
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
