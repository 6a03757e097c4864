"""What decides ``correct``: the program's first three steps against the plain
reference's, from the same weights and the same batches.

Three numbers are read; a cell's ``limits/<cell>.json`` names those compared,
each with its limit (a number for which neither the control nor a fault of
the cell gives an upper reading is read but not compared):

* ``loss_gap``: the largest relative gap between the program's loss and the
  reference's over the three steps;
* ``grad_gap``: the first gradient as the optimizer got it, read from the
  momentum after one step, by the worst leaf: ``|norm_p - norm_r|`` over the
  larger of the reference's norm of that leaf and its median leaf's;
* ``change_gap``: the same for the change of the weights over three steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (a key bias under softmax moves by round-off alone).

A leaf is one model's one weight of one layer.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

STEPS = 3
QUIET_GRAD = 1e-3            # a leaf whose gradient is this far under the median
NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def to_host(norms: Dict[str, jax.Array]) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64) for k, v in norms.items()}


def worst_leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                   skip: Optional[Dict[str, np.ndarray]] = None
                   ) -> Tuple[float, str]:
    """The worst relative gap of norms and the leaf it is on."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    med = float(np.median(np.concatenate([np.ravel(v) for v in ref.values()])))
    worst, where = 0.0, ""
    for path in sorted(ref):
        p, r = np.ravel(prog[path]), np.ravel(ref[path])
        keep = np.ones(r.shape, bool) if skip is None else ~np.ravel(skip[path])
        gap = np.abs(p - r) / np.maximum(r, med)
        gap = np.where(keep, gap, 0.0)
        if not np.all(np.isfinite(gap[keep])):
            return math.inf, path
        if gap.size and float(gap.max()) > worst:
            worst = float(gap.max())
            where = f"{path}[{int(gap.argmax())}]"
    return worst, where


def quiet_leaves(ref_grad: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    med = float(np.median(np.concatenate([np.ravel(v)
                                          for v in ref_grad.values()])))
    return {k: np.asarray(v) < QUIET_GRAD * med for k, v in ref_grad.items()}


def readings(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """``{number: (value, where)}`` from two sets of first-step readings:
    ``{"losses": [..], "grad": norms, "change": norms}``."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    loss = max(gaps) if all(map(math.isfinite, prog["losses"])) else math.inf
    k = int(np.argmax(gaps)) if math.isfinite(loss) else -1
    return {
        "loss_gap": (loss, f"step {k}"),
        "grad_gap": worst_leaf_gap(prog["grad"], ref["grad"]),
        "change_gap": worst_leaf_gap(prog["change"], ref["change"],
                                     quiet_leaves(ref["grad"])),
    }


def verdict(read: Dict[str, Tuple[float, str]],
            limits: Dict[str, float]) -> Tuple[bool, Dict]:
    """``correct`` and each number the limits name, beside its limit."""
    unknown = set(limits) - set(NUMBERS)
    if unknown or not limits:
        raise ValueError(f"limits name {sorted(limits)}; numbers are "
                         f"{NUMBERS}")
    checks = {k: {"value": read[k][0], "limit": float(limits[k]),
                  "leaf": read[k][1]} for k in NUMBERS if k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


# ----------------------------------------------------------------------------
# the reference's three steps
# ----------------------------------------------------------------------------

class ReferenceSteps:
    """The reference's readings over the program's first batches, from the
    weights a seed gives: one jitted step per batch. ``rows`` keeps only the
    first rows of each batch (the half-batch fault). Build once, call per
    seed: the jitted programs are kept."""

    def __init__(self, ref, cfg: Dict, traffic: Dict,
                 precision: str = "highest", rows: Optional[int] = None):
        self.layout = ref.param_layout(cfg)
        self.n = int(traffic["models"])
        self.rows = rows
        layout = self.layout
        self.make = jax.jit(lambda k: weights.make_params(layout, k))
        lr, mu = float(traffic["lr"]), float(traffic["momentum"])
        wd = float(traffic["weight_decay"])

        def step(peers, moms, batch):
            loss, grads = jax.value_and_grad(
                lambda ps: ref.total_loss(cfg, traffic, ps, batch, precision)
            )(peers)
            peers, moms = ref.sgd_momentum(peers, grads, moms, lr, mu, wd)
            return loss, peers, moms

        make = self.make
        self.step = jax.jit(step, donate_argnums=(0, 1))
        self.norms = jax.jit(_stack_norms)
        self.change = jax.jit(lambda ps, ks: _stack_norms(
            [jax.tree.map(lambda a, b: a - b, p, make(k))
             for p, k in zip(ps, ks)]))

    def __call__(self, seed: int, batches: List[Dict]) -> Dict:
        n = self.n
        wkey = weights.weights_key(seed)
        keys = list(weights.peer_keys(wkey, n)) if n > 1 else [wkey]
        peers = [self.make(k) for k in keys]
        moms = [jax.tree.map(jnp.zeros_like, p) for p in peers]
        losses, grad = [], None
        for k, b in enumerate(batches[:STEPS]):
            one = jax.tree.map(lambda x: x[0], b) if n > 1 else b
            if self.rows is not None:
                one = jax.tree.map(lambda x: x[:self.rows], one)
            loss, peers, moms = self.step(peers, moms, one)
            losses.append(float(loss))
            if k == 0:
                grad = to_host(self.norms(moms))
        out = {"losses": losses, "grad": grad,
               "change": to_host(self.change(peers, keys))}
        if n == 1:
            out["grad"] = {k: v[0] for k, v in out["grad"].items()}
            out["change"] = {k: v[0] for k, v in out["change"].items()}
        return out


def _stack_norms(peers: List[Dict]) -> Dict[str, jax.Array]:
    out = {}
    for path in peers[0]:
        per = []
        for p in peers:
            x = p[path].astype(jnp.float32)
            lead = int(path.startswith("layers/"))
            per.append(jnp.sqrt(jnp.sum(x * x,
                                        axis=tuple(range(lead, x.ndim)))))
        out[path] = jnp.stack(per)
    return out


def program_steps(prog, seed: int, make_batches: Callable, dkey):
    """Build the program's state from ``seed`` and drive its first three
    steps through ``bundle.apply`` with the window's own batches
    (``make_batches`` is ``traffic_gen.batch_fn``'s). Returns ``(state,
    readings, batches)``; the state goes on to the window."""
    wkey = weights.weights_key(seed)
    state = prog.init_state(wkey)
    batches = []
    while len(batches) < STEPS:
        batches.extend(make_batches(dkey, len(batches)))
    batches = batches[:STEPS]
    losses, grad = [], None
    for k, b in enumerate(batches):
        state, metrics, _ = prog.bundle.apply(state, b, k)
        losses.append(metrics["loss"])
        if k == 0:
            grad = prog.grad_norms(state)
    change = prog.change_norms(state, wkey)
    read = {"losses": [float(x) for x in losses], "grad": to_host(grad),
            "change": to_host(change)}
    return state, read, batches
