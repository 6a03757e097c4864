"""attention_share: self time of the device ops under the program's ``attn``
scope (the attention mixer with its pre-norm: forward, remat's recompute and
backward) over device 0's busy time (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, "attn")
