"""The one batch generator: batch k depends on the data key and k alone,
whichever call makes it, and no two rows of the steps it makes are alike."""
import jax
import numpy as np

from chipbench import manifest, traffic_gen, weights
from chipbench.tests import tiny


def test_batch_k_is_the_same_from_any_call_and_rows_differ():
    traffic = tiny.traffic()
    key = weights.data_key(2**32 + 7)
    four = traffic_gen.batch_fn(traffic, 4)(key, 3)
    two = traffic_gen.batch_fn(traffic, 2)(key, 5)
    for a, b in zip(four[2:], two):
        for name in ("tokens", "labels", "mask"):
            np.testing.assert_array_equal(np.asarray(a[name]),
                                          np.asarray(b[name]))
    toks = np.asarray(jax.device_get([b["tokens"] for b in four]))
    # every model gets the same batch; the rows of all steps differ
    assert toks.shape[1] == traffic["models"]
    assert (toks == toks[:, :1]).all()
    rows = toks[:, 0].reshape(-1, toks.shape[-1])
    assert len({r.tobytes() for r in rows}) == rows.shape[0]
    np.testing.assert_array_equal(np.asarray(four[0]["labels"])[..., :-1],
                                  np.asarray(four[0]["tokens"])[..., 1:])


def test_every_seed_draws_the_same_task_relabelled():
    """The chain is the traffic file's task for every seed, so no seed puts
    much more of a batch on one id than another does. 674629524 drew a
    near-absorbing chain when the task came from the seed: 666 of 6,144
    tokens on one id."""
    traffic = manifest.traffic("codist2")
    make = traffic_gen.batch_fn(traffic, 3)
    tops = []
    for seed in (674629524, 7_000_007_920, 2**32 + 7, 11):
        toks = np.concatenate([np.asarray(b["tokens"][0]).ravel()
                               for b in make(weights.data_key(seed), 0)])
        tops.append(np.bincount(toks, minlength=256).max() / toks.size)
    assert max(tops) < 0.03, tops
    # the ids are relabelled from seed to seed
    a, b = (np.asarray(make(weights.data_key(s), 0)[0]["tokens"][0])
            for s in (11, 12))
    assert np.bincount(a.ravel(), minlength=256).argmax() != \
        np.bincount(b.ravel(), minlength=256).argmax()
