"""DART boosting (Dropouts meet Multiple Additive Regression Trees).

Reference: ``src/boosting/dart.hpp:23`` — per iteration, a random subset of
existing trees is "dropped" (their contribution removed from the scores before
computing gradients), the new tree is fit to the residual, and the dropped trees
plus the new tree are re-normalized by ``k/(k+1)`` and ``1/(k+1)``.

All tree predictions/scalings below run on device arrays (``TreeArrays``); the
host only draws the dropout indices.
"""

from __future__ import annotations

import numpy as np

from .gbdt import GBDT, _scale_tree_arrays, _tree_dict
from .tree import predict_tree_bins_device


class DART(GBDT):
    _deterministic_iters = False   # drop/renorm mutates scores between iters
    _supports_iter_pack = False    # per-round host drop/renorm decisions
    _supports_checkpoint = False   # drop bookkeeping/drop_rng not captured

    def __init__(self, cfg, train, valids=(), base_model=None):
        super().__init__(cfg, train, valids, base_model=base_model)
        self.drop_rng = np.random.RandomState(cfg.drop_seed)

    def _tree_pred_idx(self, k: int, idx: int, bins):
        pred = self._tree_pred_idx_raw(k, idx, bins)
        # train bins may carry shard-padding rows (data meshes); scores do
        # not.
        if bins is self.score_bins_dev:
            return pred[:self.scores.shape[0]]
        return pred

    def _tree_pred_idx_raw(self, k: int, idx: int, bins):
        return predict_tree_bins_device(
            _tree_dict(self.dev_models[k][idx]), bins,
            self.meta_dev["nan_bins"])

    def _add_scores(self, k: int, pred) -> None:
        if self._shape_k:
            self.scores = self.scores.at[:, k].add(pred)
        else:
            self.scores = self.scores + pred

    def _add_valid(self, i: int, k: int, pred) -> None:
        if self._shape_k:
            self.valid_scores[i] = self.valid_scores[i].at[:, k].add(pred)
        else:
            self.valid_scores[i] = self.valid_scores[i] + pred

    def _scale_stored_tree(self, k: int, idx: int, factor: float) -> None:
        self.dev_models[k][idx] = _scale_tree_arrays(
            self.dev_models[k][idx], factor)
        self._host_cache[k][idx] = None

    def _scale_new_tree(self, k: int, idx: int, factor: float) -> None:
        """Scale the freshly-trained tree and fix up all score arrays."""
        delta = factor - 1.0
        self._add_scores(k, self._tree_pred_idx(k, idx, self.score_bins_dev) * delta)
        for i, vbins in enumerate(self.valid_bins):
            self._add_valid(i, k, self._tree_pred_idx(k, idx, vbins) * delta)
        self._scale_stored_tree(k, idx, factor)

    def _train_one_iter(self, grad=None, hess=None) -> bool:
        cfg = self.cfg
        n_trees = len(self.dev_models[0])
        drop_idx: list = []
        if n_trees > 0 and self.drop_rng.rand() >= cfg.skip_drop:
            if cfg.uniform_drop:
                picks = self.drop_rng.rand(n_trees) < cfg.drop_rate
                drop_idx = list(np.nonzero(picks)[0])
            else:
                k_drop = max(int(round(n_trees * cfg.drop_rate)), 1)
                drop_idx = list(self.drop_rng.choice(
                    n_trees, size=min(k_drop, n_trees), replace=False))
            if cfg.max_drop > 0:
                drop_idx = drop_idx[: cfg.max_drop]
        # Remove dropped trees' contribution before computing gradients; keep
        # the predictions — re-adding at the reduced scale reuses them.
        drop_preds: dict = {}
        for k in range(self.num_class):
            for idx in drop_idx:
                pred = self._tree_pred_idx(k, idx, self.score_bins_dev)
                drop_preds[(k, idx)] = pred
                self._add_scores(k, -pred)
        stop = super()._train_one_iter(grad, hess)
        # Normalize (reference DART::Normalize): dropped trees come back scaled
        # by k/(k+1); the new tree is scaled by 1/(k+1).
        kd = len(drop_idx)
        if kd > 0:
            if cfg.xgboost_dart_mode:
                # reference dart.hpp:140-145,179-196: shrinkage lr/(lr+k),
                # dropped trees keep k/(k+lr)
                denom = kd + cfg.learning_rate
            else:
                denom = kd + 1.0
            factor_old = kd / denom
            factor_new = 1.0 / denom
            for k in range(self.num_class):
                new_idx = len(self.dev_models[k]) - 1
                self._scale_new_tree(k, new_idx, factor_new)
                for idx in drop_idx:
                    # Tree was fully removed above; re-add at the reduced scale.
                    self._add_scores(k, drop_preds[(k, idx)] * factor_old)
                    for i, vbins in enumerate(self.valid_bins):
                        self._add_valid(
                            i, k,
                            self._tree_pred_idx(k, idx, vbins)
                            * (factor_old - 1.0))
                    self._scale_stored_tree(k, idx, factor_old)
        return stop
