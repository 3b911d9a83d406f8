"""Random Forest mode.

Reference: ``src/boosting/rf.hpp:25`` — mandatory bagging, no shrinkage,
gradients always computed at the init score (no boosting), and predictions are
the **average** of tree outputs plus the init score.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .gbdt import GBDT, _tree_dict
from .tree import predict_tree_bins_device


class RandomForest(GBDT):
    _supports_iter_pack = False    # averaged scores, per-round host bagging
    _supports_checkpoint = False   # running-average score state not captured

    def __init__(self, cfg, train, valids=(), base_model=None):
        if not (cfg.bagging_freq > 0 and (cfg.bagging_fraction < 1.0
                                          or cfg.feature_fraction < 1.0)):
            raise ValueError(
                "rf boosting requires bagging (bagging_freq>0 and "
                "bagging_fraction<1) or feature_fraction<1  "
                "(reference rf.hpp constructor check)")
        if base_model is not None:
            raise ValueError(
                "training continuation (init_model) is not supported with "
                "boosting=rf: averaged outputs cannot replay a base model "
                "through init scores")
        super().__init__(cfg, train, valids, base_model=base_model)
        # Scores are frozen at the init score; trees are averaged at predict.
        self._init_train_scores = self.scores
        self._sum_scores = jnp.zeros_like(self.scores)
        self._sum_valid = [jnp.zeros_like(v) for v in self.valid_scores]
        self._init_valid = [v for v in self.valid_scores]

    def _train_one_iter(self, grad=None, hess=None) -> bool:
        if grad is None:
            g_dev, h_dev = self._grad_fn(self._init_train_scores)
        else:
            g_dev = jnp.asarray(grad, jnp.float32).reshape(self.scores.shape)
            h_dev = jnp.asarray(hess, jnp.float32).reshape(self.scores.shape)
        mask_dev, fmask, _ = self._iter_masks(grad, hess)
        qkey = (jax.random.fold_in(self._quant_key, self.iter_)
                if self._quant_key is not None else None)

        num_leaves_flags = []
        for k in range(self.num_class):
            gk = g_dev[:, k] if self._shape_k else g_dev
            hk = h_dev[:, k] if self._shape_k else h_dev
            qk = None if qkey is None else jax.random.fold_in(qkey, k)
            zero = jnp.zeros(self.train_data.num_data, jnp.float32)
            contrib, arrays, row_leaf = self._dispatch(
                "_grow_apply", self.bins_dev, zero, gk, hk, mask_dev, fmask,
                1.0, quant_key=qk)
            self.dev_models[k].append(arrays)
            self._host_cache[k].append(None)
            num_leaves_flags.append(arrays.num_leaves)
            if self._shape_k:
                self._sum_scores = self._sum_scores.at[:, k].add(contrib)
            else:
                self._sum_scores = self._sum_scores + contrib
            dev_tree = _tree_dict(arrays)
            for i, vbins in enumerate(self.valid_bins):
                vp = predict_tree_bins_device(dev_tree, vbins,
                                              self.meta_dev["nan_bins"])
                if self._shape_k:
                    self._sum_valid[i] = self._sum_valid[i].at[:, k].add(vp)
                else:
                    self._sum_valid[i] = self._sum_valid[i] + vp
        self.iter_ += 1
        t = float(self.iter_)
        self.scores = self._init_train_scores + self._sum_scores / t
        self.valid_scores = [init + s / t for init, s in
                             zip(self._init_valid, self._sum_valid)]
        nls = jax.device_get(num_leaves_flags)
        return all(int(x) <= 1 for x in nls)

    def predict_raw(self, X, num_iteration=None, start_iteration=0):
        raw = super().predict_raw(X, num_iteration, start_iteration)
        n_iter = len(self.dev_models[0]) if num_iteration is None else num_iteration
        n_iter = max(min(n_iter, len(self.dev_models[0]) - start_iteration), 1)
        init = self.init_scores[0] if self.num_class == 1 else self.init_scores
        return (raw - init) / n_iter + init
