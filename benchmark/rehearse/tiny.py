"""The cells at tiny widths, for rehearsals and tests off the chip: the
same files, shrunk in memory. Never a source of a device number."""

TINY_CONFIG = {
    "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 512,
    "max_position_embeddings": 256, "sliding_window": 256,
}


def shrink(files: dict, dtype: str = "float32") -> None:
    files["config"].update(TINY_CONFIG)
    files["config"].pop("head_dim", None)
    mix = files["mix"]
    mix["job"].update(MAX_SEQ_LENGTH=128, LORA_R=8, TRAIN_DTYPE=dtype,
                      NUM_TRAIN_SAMPLES=256, AOT_TRAIN_STEP=False)
    mix["rows"].update(count=256, length={
        "dist": "lognormal", "median": 40, "sigma": 0.6,
        "min": 8, "max": 128})
