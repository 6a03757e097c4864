"""recompute_share: self time of the device ops that remat recomputes (their
path holds ``rematted_computation``) over device 0's busy time. A cut by
pass, not by layer: it overlaps ``attention_share`` and ``mlp_share``
(``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, scopes.REMAT)
