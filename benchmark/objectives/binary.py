"""Binary log-loss as LightGBM defines it (``BinaryLogloss``)."""

import numpy as np


def binary_init_score(label: np.ndarray, sigmoid: float = 1.0) -> float:
    """LightGBM's boost-from-average for binary log-loss."""
    pavg = float(np.mean(label > 0))
    pavg = min(max(pavg, 1e-15), 1 - 1e-15)
    return float(np.log(pavg / (1.0 - pavg)) / sigmoid)


def binary_gradients(score: np.ndarray, label: np.ndarray,
                     sigmoid: float = 1.0):
    y = np.where(label > 0, 1.0, -1.0)
    response = -y * sigmoid / (1.0 + np.exp(y * sigmoid * score))
    abs_r = np.abs(response)
    return response, abs_r * (sigmoid - abs_r)


class Binary:
    def __init__(self, params: dict, label: np.ndarray):
        self.label = label
        self.sigmoid = float(params.get("sigmoid", 1.0))

    def init_score(self) -> float:
        return binary_init_score(self.label, self.sigmoid)

    def gradients(self, score: np.ndarray):
        return binary_gradients(score, self.label, self.sigmoid)


def make(params: dict, label: np.ndarray, group=None) -> Binary:
    return Binary(params, label)
