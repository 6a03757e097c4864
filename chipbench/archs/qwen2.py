"""The program's model for a configuration file of ``model_type`` ``qwen2``:
the program's registered architecture (``run.program_arch``) with the file's
sizes. A configuration of another ``model_type`` brings a file of its own
beside this one, with the same ``program_config``."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict


def program_config(cfg: Dict, base: Any) -> Any:
    """``base`` is the program's ``ModelConfig`` for ``run.program_arch``."""
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: qwen2 maps only hidden_act silu")
    run = cfg["run"]
    return dataclasses.replace(
        base,
        family="dense",
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg["qkv_bias"]),
        act="silu",
        sliding_window=0,
        moe=None,
        dtype=run["activation_dtype"],
        param_dtype=run["param_dtype"],
        vocab_pad_multiple=run["vocab_pad_multiple"],
    )
