"""Row sampling strategies: bagging and GOSS.

Reference: ``SampleStrategy`` factory (``include/LightGBM/sample_strategy.h:23``,
``src/boosting/sample_strategy.cpp:14``) with ``BaggingSampleStrategy``
(``bagging.hpp``) and ``GOSSStrategy`` (``goss.hpp``).

TPU re-design: the reference materializes index subsets and copies rows
(``Dataset::CopySubrow``).  Here a sample has TWO forms, and the growth plan
(``models/capabilities.plan_growth``, ``plan.sampling``) says which one a
composition runs:

- ``subset`` — device GOSS on the single-device wave body (the permutation
  layout): the selection yields the in-bag ROW IDS at their static length
  ``top_k + other_k`` beside the weights, and the tree is grown over those
  rows alone — their bins and values are copied out once a tree (the
  reference's ``CopySubrow``), so the root histogram, the partition's ladder
  and every per-leaf gather are sized by the in-bag count; out-of-bag rows
  reach their leaf for the score update by a dense per-wave update of a
  full-length row -> leaf vector (``models/grower.py``).
- ``mask`` — everything else (a device mesh, the mask body under 2 048 rows
  a shard, the host sampler ``tpu_device_goss=off``, plain and balanced
  bagging, the streamed trainer): a **multiplicative row mask**, every
  shape static at ``N`` — out-of-bag rows contribute zero gradient/hessian
  and zero count to histograms, which is numerically identical and costs a
  full-row growth.

Either way GOSS's amplification ``(N - top_k) / other_k`` is a per-row
weight, and the first ``int(1 / learning_rate)`` iterations take every row
(``goss.hpp``: ``if (iter < static_cast<int>(1.0f / learning_rate)) return``).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .config import Config


class SampleStrategy:
    """Produces the per-iteration row mask (1.0 in-bag, 0.0 out, >1.0 GOSS boost)."""

    def __init__(self, cfg: Config, num_data: int,
                 label: Optional[np.ndarray] = None,
                 query_boundaries: Optional[np.ndarray] = None):
        self.cfg = cfg
        self.num_data = num_data
        self.label = label
        self.query_boundaries = query_boundaries
        self.rng = np.random.RandomState(cfg.bagging_seed)
        self.is_goss = cfg.data_sample_strategy == "goss"
        balanced = (cfg.pos_bagging_fraction < 1.0
                    or cfg.neg_bagging_fraction < 1.0)
        self.is_bagging = (not self.is_goss) and (
            (cfg.bagging_fraction < 1.0 and cfg.bagging_freq > 0) or balanced)
        self.is_balanced = balanced and not self.is_goss
        self._cached: Optional[np.ndarray] = None

    @property
    def goss_unsampled_iters(self) -> int:
        """Iterations GOSS leaves unsampled: LightGBM's
        ``int(1 / learning_rate)`` (``goss.hpp``)."""
        return int(1.0 / self.cfg.learning_rate)

    def goss_samples_at(self, iteration: int) -> bool:
        return self.is_goss and iteration >= self.goss_unsampled_iters

    def needs_resample(self, iteration: int) -> bool:
        if self.is_goss:
            return True
        if not self.is_bagging:
            return False
        freq = max(self.cfg.bagging_freq, 1)
        return iteration % freq == 0 or self._cached is None

    def mask(self, iteration: int, grad: Optional[np.ndarray] = None,
             hess: Optional[np.ndarray] = None) -> Optional[np.ndarray]:
        """Return the (N,) f32 mask for this iteration, or None (all rows)."""
        if self.is_goss:
            if not self.goss_samples_at(iteration):
                return None
            return self._goss_mask(grad, hess)
        if not self.is_bagging:
            return None
        if self.needs_resample(iteration):
            self._cached = self._bagging_mask()
        return self._cached

    def _bagging_mask(self) -> np.ndarray:
        cfg = self.cfg
        n = self.num_data
        mask = np.zeros(n, np.float32)
        if cfg.bagging_by_query and self.query_boundaries is not None:
            nq = len(self.query_boundaries) - 1
            take = self.rng.rand(nq) < cfg.bagging_fraction
            for qi in np.nonzero(take)[0]:
                mask[self.query_boundaries[qi]: self.query_boundaries[qi + 1]] = 1.0
            return mask
        if self.is_balanced and self.label is not None:
            pos = self.label > 0
            r = self.rng.rand(n)
            mask[(pos) & (r < cfg.pos_bagging_fraction)] = 1.0
            mask[(~pos) & (r < cfg.neg_bagging_fraction)] = 1.0
            return mask
        k = int(n * cfg.bagging_fraction)
        idx = self.rng.choice(n, size=k, replace=False)
        mask[idx] = 1.0
        return mask

    def goss_constants(self):
        """(top_k, other_k, amplification) — shared by the host and device
        GOSS paths (reference goss.hpp: ``multiply = (cnt - top_k) /
        other_k``)."""
        cfg = self.cfg
        n = self.num_data
        top_k = max(int(n * cfg.top_rate), 1)
        other_k = int(n * cfg.other_rate)
        amp = (n - top_k) / other_k if other_k > 0 else 0.0
        return top_k, other_k, amp

    def _goss_mask(self, grad: np.ndarray, hess: np.ndarray) -> np.ndarray:
        """GOSS (reference ``goss.hpp:30-60``): keep the top ``top_rate`` fraction
        by |grad*hess|, sample ``other_rate`` of the rest and up-weight them."""
        cfg = self.cfg
        n = self.num_data
        score = np.abs(grad * hess)
        top_k, other_k, amp = self.goss_constants()
        order = np.argsort(-score, kind="stable")
        mask = np.zeros(n, np.float32)
        mask[order[:top_k]] = 1.0
        rest = order[top_k:]
        if len(rest) > 0 and other_k > 0 and cfg.other_rate > 0:
            pick = self.rng.choice(len(rest), size=min(other_k, len(rest)),
                                   replace=False)
            mask[rest[pick]] = amp
        return mask


def goss_sample_device(grad_sum, hess_sum, key, top_k: int, other_k: int,
                       amplify: float, with_rows: bool = True):
    """Device-resident GOSS (reference ``goss.hpp``) — no host round-trip:
    exact top-k by ``|grad * hess|``, gumbel-style uniform top-k over a
    fresh draw for the random remainder.  Returns ``(mask, rows)``: the
    (N,) weights (1 / ``amplify`` / 0) and, ``with_rows``, the in-bag row
    ids ascending at their static length ``top_k + other_k`` — the two
    ``lax.top_k`` hand their indices over with their values, so the ids
    cost one sort of the sample and no pass over all rows (on a v5e at
    2.27 M rows the two ``top_k`` take 1.4 ms; compacting a mask instead
    takes 4.2 ms by a sort and 13.9 by prefix count + scatter: PERF.md,
    Findings PR 33).  A slot no row fills (fewer than ``other_k`` rows
    outside the top set) holds ``N``, the growers' phantom zero row."""
    import jax
    import jax.numpy as jnp

    n = grad_sum.shape[0]
    score = jnp.abs(grad_sum * hess_sum)
    _, rows = jax.lax.top_k(score, top_k)
    mask = jnp.zeros(n, jnp.float32).at[rows].set(1.0)
    if other_k > 0:
        u = jax.random.uniform(key, (n,))
        u = jnp.where(mask > 0.0, -1.0, u)       # exclude the top set
        sel_vals, sel_idx = jax.lax.top_k(u, other_k)
        # drop slots that fell back onto excluded rows (rest smaller than
        # other_k)
        tgt = jnp.where(sel_vals >= 0.0, sel_idx, n)
        mask = mask.at[tgt].set(jnp.float32(amplify), mode="drop")
        rows = jnp.concatenate([rows, tgt])
    return mask, (jnp.sort(rows).astype(jnp.int32) if with_rows else None)


def goss_mask_device(grad_sum, hess_sum, key, top_k: int, other_k: int,
                     amplify: float):
    """The mask form of ``goss_sample_device``."""
    return goss_sample_device(grad_sum, hess_sum, key, top_k, other_k,
                              amplify, with_rows=False)[0]


def _rank_select_device(u, valid, k):
    """Boolean mask keeping the k smallest draws among ``valid`` entries —
    the device analog of ``rng.choice(valid, k, replace=False)`` (exact
    subset size, matching the reference's index-subset bagging rather than
    per-row Bernoulli)."""
    import jax.numpy as jnp

    n = u.shape[0]
    u = jnp.where(valid, u, 2.0)              # invalid entries sort last
    order = jnp.argsort(u)
    rank = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    return valid & (rank < k)


def bagging_mask_device(key, epoch, num_data: int, bag_k: int):
    """In-scan bagging row mask for the iteration-packed path: key-folded by
    the resample epoch (``iteration // bagging_freq``), so every iteration
    inside a pack derives the SAME mask its epoch demands — the device
    analog of ``SampleStrategy.mask``'s host cache, with
    ``jax.random.fold_in`` replacing the host RNG stream."""
    import jax
    import jax.numpy as jnp

    k2 = jax.random.fold_in(key, epoch)
    u = jax.random.uniform(k2, (num_data,))
    sel = _rank_select_device(u, jnp.ones(num_data, bool), bag_k)
    return sel.astype(jnp.float32)


def feature_mask_device(key, iteration, base_mask, keep_k: int):
    """In-scan per-tree ``feature_fraction`` mask (device analog of
    ``FeatureSampler.tree_mask``): keep exactly ``keep_k`` of the base-mask
    features, drawn from a key folded with the iteration number."""
    import jax

    k2 = jax.random.fold_in(key, iteration)
    u = jax.random.uniform(k2, base_mask.shape)
    return _rank_select_device(u, base_mask, keep_k)


class FeatureSampler:
    """``feature_fraction`` per tree + interaction constraints
    (reference ``ColSampler``, ``col_sampler.hpp``)."""

    def __init__(self, cfg: Config, num_features: int):
        self.cfg = cfg
        self.num_features = num_features
        self.rng = np.random.RandomState(cfg.feature_fraction_seed)
        self.used = np.ones(num_features, bool)
        # Interaction constraint groups (reference ColSampler ctor,
        # col_sampler.hpp:27-30).  The per-BRANCH narrowing (a node may only
        # split on its branch features plus groups containing the whole
        # branch) lives in the grower; here the tree-level mask is the union
        # of all groups, which equals the root's allowed set.
        self.interaction_groups = None
        if cfg.interaction_constraints:
            groups = []
            for grp in cfg.interaction_constraints:
                ids = tuple(int(tok) for tok in str(grp).strip("[] ").split(",")
                            if tok.strip())
                if ids:
                    groups.append(ids)
            if groups:
                self.interaction_groups = tuple(groups)
                allowed = sorted({i for g in groups for i in g})
                self.used = np.zeros(num_features, bool)
                self.used[allowed] = True

    def tree_mask(self, iteration: int) -> np.ndarray:
        frac = self.cfg.feature_fraction
        base = self.used.copy()
        if frac >= 1.0:
            return base
        valid = np.nonzero(base)[0]
        k = max(int(np.ceil(len(valid) * frac)), 1)
        pick = self.rng.choice(valid, size=k, replace=False)
        mask = np.zeros(self.num_features, bool)
        mask[pick] = True
        return mask
