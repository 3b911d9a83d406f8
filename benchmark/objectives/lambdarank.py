"""LambdaRank with NDCG deltas as LightGBM defines it (``LambdarankNDCG``,
src/objective/rank_objective.hpp)."""

import numpy as np


def lambdarank_gradients(score: np.ndarray, label: np.ndarray,
                         group: np.ndarray, sigmoid: float = 1.0,
                         truncation: int = 30, norm: bool = True,
                         chunk: int = 1024):
    """LambdaRank with NDCG deltas (LightGBM ``LambdarankNDCG``): for every
    query, pairs (i, j) with i among the ``truncation`` best-scored
    documents, j ranked below i, and different labels; label gain 2**l - 1,
    discount 1/log2(rank + 2), deltas over the query's ideal DCG at the
    truncation level; per-query normalisation by log2(1 + S)/S with S the
    accumulated |lambda| over both ends of every pair and, under ``norm``,
    each delta divided by 0.01 + |score difference|.  Ties in score keep
    document order (a stable sort)."""
    n = score.shape[0]
    grad = np.zeros(n, np.float64)
    hess = np.zeros(n, np.float64)
    starts = np.concatenate([[0], np.cumsum(group)]).astype(np.int64)
    sizes = np.asarray(group, np.int64)
    for size in np.unique(sizes):
        qs = np.nonzero(sizes == size)[0]
        s = int(size)
        trunc = min(truncation, s)
        disc = 1.0 / np.log2(np.arange(s) + 2.0)
        for lo in range(0, len(qs), chunk):
            q = qs[lo:lo + chunk]
            idx = starts[q][:, None] + np.arange(s)[None, :]       # (Q, S)
            sc = score[idx].astype(np.float64)
            gain = 2.0 ** label[idx] - 1.0
            ideal = -np.sort(-gain, axis=1)
            max_dcg = (ideal[:, :trunc] * disc[None, :trunc]).sum(axis=1)
            inv_max = np.where(max_dcg > 0, 1.0 / np.maximum(max_dcg, 1e-20),
                               0.0)
            order = np.argsort(-sc, axis=1, kind="stable")
            rank_of = np.argsort(order, axis=1, kind="stable")
            doc_disc = disc[rank_of]
            top = order[:, :trunc]                                   # (Q, T)
            take = lambda a: np.take_along_axis(a, top, axis=1)
            d_gain = take(gain)[:, :, None] - gain[:, None, :]      # (Q,T,S)
            d_score = take(sc)[:, :, None] - sc[:, None, :]
            d_disc = np.abs(take(doc_disc)[:, :, None]
                            - doc_disc[:, None, :])
            i_rank = np.arange(trunc)[None, :, None]
            ok = (d_gain != 0) & (rank_of[:, None, :] > i_rank)
            s_hl = np.where(d_gain > 0, d_score, -d_score)
            delta = np.abs(d_gain) * d_disc * inv_max[:, None, None]
            if norm:
                # LightGBM regularises the delta by the score distance
                # wherever the query's scores are not all equal
                spread = (sc.max(axis=1) != sc.min(axis=1))[:, None, None]
                delta = np.where(spread, delta / (0.01 + np.abs(d_score)),
                                 delta)
            p = 1.0 / (1.0 + np.exp(sigmoid * s_hl))
            lam = np.where(ok, -sigmoid * p * delta, 0.0)
            hes = np.where(ok, sigmoid * sigmoid * p * (1.0 - p) * delta, 0.0)
            sign = np.where(d_gain > 0, 1.0, -1.0)
            lam_i = (sign * lam).sum(axis=2)                         # (Q, T)
            lam_j = -(sign * lam).sum(axis=1)                        # (Q, S)
            hes_i = hes.sum(axis=2)
            hes_j = hes.sum(axis=1)
            if norm:
                tot = 2.0 * np.abs(lam).sum(axis=(1, 2))
                scale = np.where(tot > 0,
                                 np.log2(1.0 + tot) / np.maximum(tot, 1e-300),
                                 1.0)[:, None]
            else:
                scale = 1.0
            g = lam_j * scale
            h = hes_j * scale
            np.add.at(g, (np.arange(len(q))[:, None], top), lam_i * scale)
            np.add.at(h, (np.arange(len(q))[:, None], top), hes_i * scale)
            grad[idx] = g
            hess[idx] = h
    return grad, hess


class Lambdarank:
    def __init__(self, params: dict, label: np.ndarray, group: np.ndarray):
        self.label, self.group = label, group
        self.sigmoid = float(params.get("sigmoid", 1.0))
        self.truncation = int(params.get("lambdarank_truncation_level", 30))
        self.norm = bool(params.get("lambdarank_norm", True))

    def init_score(self) -> float:
        return 0.0

    def gradients(self, score: np.ndarray):
        return lambdarank_gradients(score, self.label, self.group,
                                    self.sigmoid, self.truncation, self.norm)


def make(params: dict, label: np.ndarray, group: np.ndarray) -> Lambdarank:
    return Lambdarank(params, label, group)
