"""optimizer_share: self time of the device ops under the program's
``optimizer`` scope (the update of every weight and its optimizer state)
over device 0's busy time (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.share(ctx, "optimizer")
