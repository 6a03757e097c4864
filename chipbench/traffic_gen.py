"""The one batch generator: every traffic file under ``traffic/`` is read here.

Token streams come from a first-order Markov chain over the first
``vocab_ids`` ids, as ``repro.data.MarkovLM`` draws them (copied here so that
the yardstick cannot change with the program): the transition matrix
``normal / concentration`` of the task ``MarkovLM(seed=task_seed)``, a uniform
first token, then one categorical draw per position. The task is the traffic
file's, the same for every seed; the data key relabels its ids by a
permutation and draws the first tokens and the steps. So every seed trains the
same chain in another order. (A task drawn from the seed gave, on about one
seed in 25, an id that follows itself so often that it took a tenth of a
batch's tokens and a quarter of some rows: harder work than the others'.)
Batch k is a pure
function of the data key and k, whatever call makes it, and its rows draw
from keys of their own, so every row of every step differs. Every model gets
the same batch (the paper's coordinated sampling).
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp


def batch_fn(traffic: Dict, n: int) -> Callable[[jax.Array, int], Tuple]:
    """``(data_key, first_step) -> (batch first_step, ..., batch first_step +
    n - 1)`` as one jitted call: one program execution makes ``n`` steps'
    batches, each in buffers of its own. With more than one model a batch
    carries a leading model axis."""
    v = int(traffic["vocab_ids"])
    conc = float(traffic["concentration"])
    task = int(traffic["task_seed"])
    b, s, m = int(traffic["batch"]), int(traffic["seq"]), int(traffic["models"])

    def one(key, step):
        trans = jax.random.normal(jax.random.key(task), (v, v)) / conc
        ids = jax.random.permutation(jax.random.fold_in(key, 0), v)
        k = jax.random.fold_in(jax.random.fold_in(key, 1), step)
        k0, k1 = jax.random.split(k)
        first = jax.random.randint(k0, (b,), 0, v)

        def draw(tok, kk):
            nxt = jax.random.categorical(kk, trans[tok]).astype(jnp.int32)
            return nxt, nxt

        _, rest = jax.lax.scan(draw, first.astype(jnp.int32),
                               jax.random.split(k1, s))
        toks = ids.astype(jnp.int32)[
            jnp.concatenate([first[None].astype(jnp.int32), rest]).T]
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
               "mask": jnp.ones((b, s), jnp.float32)}
        if m > 1:
            out = jax.tree.map(lambda x: jnp.broadcast_to(x, (m,) + x.shape),
                               out)
        return out

    def chipbench_batch(key, first_step):
        many = jax.vmap(one, in_axes=(None, 0))(
            key, first_step + jnp.arange(n, dtype=jnp.int32))
        return tuple(jax.tree.map(lambda x: x[i], many) for i in range(n))

    return jax.jit(chipbench_batch)
