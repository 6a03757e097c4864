"""The sizes a dense decoder's configuration file gives, under the short names
the reference and the operation counts share."""
from __future__ import annotations

from typing import Dict


def dims(cfg: Dict) -> Dict[str, int]:
    """Width ``d``, heads ``h`` and ``kv``, head size ``hd``, MLP width
    ``ff``, layers ``L`` and the padded vocabulary ``V``."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    pad = cfg["run"]["vocab_pad_multiple"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"], "hd": d // h,
            "ff": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": -(-cfg["vocab_size"] // pad) * pad}
