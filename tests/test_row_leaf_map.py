"""The row -> leaf map of the permutation layout, without growing a tree.

``grower._row_leaf_map`` turns the final grouped ``perm`` and the leaves'
``[start, start + rows)`` ranges into each row's leaf id with one scatter
of the live leaves' starts and one prefix sum.  The reference here is the
per-position search it replaced — numpy ``searchsorted`` over the sorted
live starts — and every case must agree with it bit for bit.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.models.grower import _row_leaf_map


def _tiling(rng, n, L, live, empty=0, shuffle=True):
    """``live`` leaves tiling ``[0, n)``, ``empty`` of them zero-row and
    sharing their start with the sibling after them; the ``L - live``
    inactive slots hold garbage."""
    cuts = np.sort(rng.choice(np.arange(1, n), size=live - empty - 1,
                              replace=False))
    starts = np.concatenate([[0], cuts]).astype(np.int64)
    rows = np.diff(np.concatenate([starts, [n]]))
    at = rng.randint(0, starts.size, size=empty)    # a sibling's start
    starts = np.concatenate([starts, starts[at]])
    rows = np.concatenate([rows, np.zeros(empty, np.int64)])
    ids = rng.permutation(live) if shuffle else np.arange(live)
    leaf_start = rng.randint(0, 2 * n, size=L)
    leaf_rows = rng.randint(0, 2 * n, size=L)
    leaf_start[ids] = starts
    leaf_rows[ids] = rows
    return leaf_start.astype(np.int32), leaf_rows.astype(np.int32)


def _reference(leaf_start, leaf_rows, num_leaves, perm, n, sentinel):
    L = leaf_start.shape[0]
    starts = np.where((np.arange(L) < num_leaves) & (leaf_rows > 0),
                      leaf_start, sentinel)
    order = np.argsort(starts, kind="stable")
    at = np.searchsorted(starts[order], np.arange(n), side="right") - 1
    out = np.zeros(n, np.int32)
    out[perm[:n]] = order[np.clip(at, 0, L - 1)]
    return out


# name: (n, L, live leaves, zero-row live leaves, shuffled ids, perm tail)
CASES = {
    "root_only": (1000, 31, 1, 0, True, 256),
    "leaves255_shuffled": (3000, 255, 255, 0, True, 512),
    "leaves255_in_id_order": (3000, 255, 255, 0, False, 512),
    "zero_row_siblings": (3000, 63, 40, 9, True, 512),
    "garbage_inactive_slots": (2048, 255, 17, 3, True, 256),
    "n_not_multiple_of_128": (1501, 31, 31, 0, True, 2048),
    "n_below_one_row_of_lanes": (100, 15, 7, 2, True, 128),
    "sentinel_in_the_padding": (1300, 31, 20, 4, True, 0),
    "leaves1023": (5000, 1023, 1023, 0, True, 1024),
    "leaves1023_half_grown": (5000, 1023, 400, 60, True, 1024),
}


@pytest.mark.parametrize("name", list(CASES))
def test_row_leaf_map_is_the_search(name):
    n, L, live, empty, shuffle, tail = CASES[name]
    rng = np.random.RandomState(len(name) + n)
    leaf_start, leaf_rows = _tiling(rng, n, L, live, empty, shuffle)
    perm = np.concatenate([rng.permutation(n),
                           np.full(tail, n)]).astype(np.int32)
    sentinel = n + tail
    want = _reference(leaf_start, leaf_rows, live, perm, n, sentinel)
    got = _row_leaf_map(jnp.asarray(leaf_start), jnp.asarray(leaf_rows),
                        jnp.asarray(live, jnp.int32), jnp.asarray(perm),
                        n, sentinel)
    assert got.dtype == jnp.int32 and got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), want)
    # every live leaf with rows holds exactly its rows
    rows = np.bincount(want, minlength=L)
    has = (np.arange(L) < live) & (leaf_rows > 0)
    np.testing.assert_array_equal(rows[has], leaf_rows[has])
    assert rows[~has].sum() == 0
