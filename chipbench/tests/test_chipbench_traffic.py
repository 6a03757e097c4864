"""The one batch generator: batch k depends on the data key and k alone,
whichever call makes it, and no two rows of the steps it makes are alike."""
import jax
import numpy as np

from chipbench import traffic_gen, weights
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
