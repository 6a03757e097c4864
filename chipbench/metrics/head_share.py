"""head_share: self time of the device ops under the program's ``embed`` or
``head`` scope (the vocabulary layer: the lookup, the final norm, the head's
matmuls and their gradients) over device 0's busy time
(``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, "embed", "head")
