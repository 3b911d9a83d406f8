"""Quantized training held to the benchmark's plain reference (PR 37).

``lgb.train`` with ``use_quantized_grad`` grows its trees on int8 gradient
and hessian levels; ``GBDT.last_levels()`` hands over what the last
iteration grew them on, and ``benchmark/compare_quantized.py`` holds the
levels to the reference's own float64 gradients (scales, floor / ceil,
unbiased rounding, fresh draws) and the trees to the sums of those levels.
Pins: a sound run passes the configuration's limits and each of three
controls fails the number named for it; the door's levels are a fresh
``ops/quantize.discretize_gradients`` at the iteration's key; the door is
``None`` and costs nothing when quantized training is off.
"""

import copy
import json
import os
import sys

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.quantize import discretize_gradients, gradient_scales
from lightgbm_tpu.telemetry import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import compare_quantized, generators  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs", "epsilon_q.json")) as f:
    CONFIG = json.load(f)
N, F, TREES, SEED = 4000, 40, 3, 2 ** 31 + 77
SMALL = {"num_leaves": 31, "min_sum_hessian_in_leaf": 2.0}


def _config():
    cfg = copy.deepcopy(CONFIG)
    cfg["params"].update(SMALL)
    cfg["correct"].update(score_sample_rows=N, sampled_nodes_per_tree=8)
    return cfg


_RUNS = {}


def _train(extra=()):
    """``(data, trees, final scores, levels of every tree)`` of a small
    quantized run through ``lgb.train``; the levels are read before each
    iteration (a before-iteration callback: the per-round path) and after
    the last."""
    key = tuple(sorted(dict(extra).items()))
    if key in _RUNS:
        return _RUNS[key]
    data = generators.make("epsilon_like", SEED, rows=N, features=F,
                           data_seed=24)
    params = dict(_config()["params"], **dict(extra))
    levels = {}

    def grab(env):
        if env.iteration > 0:
            levels[env.iteration - 1] = env.model._gbdt.last_levels()
    grab.before_iteration = True
    bst = lgb.train(params, lgb.Dataset(data["X"], label=data["label"]),
                    TREES, callbacks=[grab])
    levels[TREES - 1] = bst._gbdt.last_levels()
    out = (data, bst.dump_model()["tree_info"],
           np.asarray(jax.device_get(bst._gbdt.scores)), levels)
    _RUNS[key] = out
    return out


CASES = {
    # case: (program params, the number that must fail, read of whom)
    "sound": ({}, None, "program"),
    "two_levels": ({"num_grad_quant_bins": 2}, ("quant_scale_gap",),
                   "program"),
    "round_to_nearest": ({"stochastic_rounding": False}, ("quant_bias_z",),
                         "program"),
    "bfloat16_sums": ({}, ("leaf_value_gap", "split_gain_gap"), "control"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_quantized_run_against_the_reference(case):
    extra, must_fail, whose = CASES[case]
    data, trees, scores, levels = _train(extra)
    assert len(trees) == TREES and sorted(levels) == list(range(TREES))
    cfg = _config()
    readings = compare_quantized.compare(
        cfg, data, trees, scores, SEED, sorted(levels), levels,
        stand_ins=whose != "program")
    limits = cfg["correct"]["limits"]
    ok, compared = compare_quantized.verdict(readings[whose], limits)
    if must_fail is None:
        assert ok, compared
        assert set(compared) == set(compare_quantized.NUMBERS[:6]) | {
            "quant_scale_gap", "quant_level_gap", "quant_bias_z",
            "quant_draw_gap"}
        # float32 tightness: the sums are the program's integers
        assert readings["program"]["leaf_value_gap"] < 1e-5
        # every planted fault fails at least one number
        for who, got in readings.items():
            if who.startswith("fault_"):
                assert any(v > limits[k] for k, v in got.items()
                           if k in limits), (who, got)
        return
    over = [k for k in must_fail if readings[whose][k] > limits[k]]
    assert over, (case, {k: readings[whose][k] for k in must_fail})


def test_the_door_hands_over_a_fresh_discretization():
    data = generators.make("epsilon_like", SEED, rows=N, features=F,
                           data_seed=24)
    params = _config()["params"]
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        data["X"], label=data["label"]))
    g = bst._gbdt
    assert g.fused_path_active
    for it in range(2):
        before = g.scores
        bst.update()
        gq, hq, sg, sh = g.last_levels()
        grad, hess = g.objective.get_gradients(before)
        a, b = gradient_scales(grad, hess, params["num_grad_quant_bins"])
        fq, fh = discretize_gradients(
            grad, hess, a, b, jax.random.fold_in(g._quant_key, it),
            params["stochastic_rounding"])
        assert gq.dtype == np.int8 and gq.shape == (N,)
        assert np.array_equal(gq, np.asarray(fq))
        assert np.array_equal(hq, np.asarray(fh))
        assert (float(sg), float(sh)) == (float(a), float(b))
    gauges = registry().snapshot()["gauges"]
    assert gauges["hist.dtype"] == 8
    assert gauges["quant.bins"] == 4 and gauges["quant.stochastic"] == 1


def _lowered(params, data):
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        data["X"], label=data["label"]))
    g = bst._gbdt
    qkey = (None if g._quant_key is None
            else jax.random.fold_in(g._quant_key, 0))
    low = g._fused_iter.lower(g.bins_dev, g.scores, g._full_mask,
                              g._tree_fmask(), 0.1, qkey, None)
    return bst, low


def test_the_door_is_none_and_free_when_quantized_training_is_off():
    data = generators.make("epsilon_like", SEED, rows=N, features=F,
                           data_seed=24)
    plain = dict(_config()["params"], use_quantized_grad=False)
    bst, low = _lowered(plain, data)
    bst.update()
    assert bst._gbdt.last_levels() is None
    assert registry().snapshot()["gauges"]["hist.dtype"] == 32
    # the unquantized program: the scores and one (tree, row -> leaf), no
    # more outputs, no int8 operand, no quantize scope
    scores, outs = jax.tree_util.tree_unflatten(
        low.out_tree, range(low.out_tree.num_leaves))
    assert len(outs) == 1 and len(outs[0]) == 2
    text = low.as_text(debug_info=True)
    assert "/quantize/" not in text and "xi8>" not in text
    # the quantized one: the same plus the levels, LAST, under the segment
    _, qlow = _lowered(_config()["params"], data)
    *rest, lv = jax.tree_util.tree_unflatten(
        qlow.out_tree, range(qlow.out_tree.num_leaves))
    assert len(rest) == 2 and len(lv) == 1 and len(lv[0]) == 4
    assert "/quantize/" in qlow.as_text(debug_info=True)
