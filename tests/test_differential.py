"""Differential harness vs the GENUINE LightGBM binary.

Trains the same data/params through our framework and the reference CLI
(built from ``/root/reference`` via ``tools/refbuild/build_reference.sh``)
and compares holdout quality. Opt-in like the live interop test: set
``LGBM_REFERENCE_BIN`` to the binary's path; skipped otherwise so CI does
not depend on a from-source C++ build.

These are QUALITY-parity checks (same data, same params, tolerance on the
holdout metric), not tree-identity checks — tree identity at depth is
covered by ``test_interop.py`` (first-tree splits) and the bench-config
AUC pin (``tests/fixtures/bench_auc.json``).
"""

import os
import shutil
import subprocess
import tempfile

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.metrics import _auc

BIN = os.environ.get("LGBM_REFERENCE_BIN")

pytestmark = pytest.mark.skipif(
    not BIN, reason="set LGBM_REFERENCE_BIN to a reference CLI binary")

N_TRAIN, N_VALID, SEED = 16_000, 4_000, 0


def _data(objective, n_features=12, n_classes=3):
    rng = np.random.RandomState(SEED)
    n = N_TRAIN + N_VALID
    X = rng.randn(n, n_features)
    logits = X[:, 0] - 0.7 * X[:, 1] + 0.4 * X[:, 2] * X[:, 3]
    if objective.startswith("multiclass"):
        y = np.clip((logits - logits.mean()) / logits.std() + 1.5, 0,
                    n_classes - 1).round()
    elif objective == "binary":
        y = (logits + 0.3 * rng.randn(n) > 0).astype(float)
    else:
        y = logits + 0.1 * rng.randn(n)
    return X, y


def _cli(conf_path):
    """Run the reference CLI surfacing its own stderr on failure."""
    proc = subprocess.run([BIN, f"config={conf_path}"], capture_output=True,
                          text=True)
    assert proc.returncode == 0, (
        f"reference CLI failed ({proc.returncode}): {proc.stderr[-2000:]}")


def _run_reference(X, y, params, pred_X, n_train=None, query=None,
                   weight=None):
    """Train + raw-predict through the reference CLI.  ``query`` is an
    optional (train_groups, pred_groups) pair written as .query sidecars
    (ranking objectives); ``weight`` an optional train-weight sidecar."""
    n_train = N_TRAIN if n_train is None else n_train
    d = tempfile.mkdtemp()
    try:
        def save(path, X_, y_):
            np.savetxt(path, np.column_stack([y_, X_]), delimiter=",",
                       fmt="%.17g")

        save(f"{d}/tr.csv", X[:n_train], y[:n_train])
        save(f"{d}/va.csv", pred_X, np.zeros(len(pred_X)))
        if query is not None:
            np.savetxt(f"{d}/tr.csv.query", query[0], fmt="%d")
            np.savetxt(f"{d}/va.csv.query", query[1], fmt="%d")
        if weight is not None:
            np.savetxt(f"{d}/tr.csv.weight", weight[:n_train], fmt="%.17g")
        conf = "".join(f"{k} = {v}\n" for k, v in params.items())
        with open(f"{d}/train.conf", "w") as fh:
            fh.write(conf + f"data = {d}/tr.csv\noutput_model = {d}/m.txt\n")
        _cli(f"{d}/train.conf")
        with open(f"{d}/pred.conf", "w") as fh:
            fh.write(f"task = predict\ndata = {d}/va.csv\n"
                     f"input_model = {d}/m.txt\noutput_result = {d}/p.txt\n"
                     "predict_raw_score = true\n")
        _cli(f"{d}/pred.conf")
        return np.loadtxt(f"{d}/p.txt")
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _run_ours(X, y, params):
    ds = lgb.Dataset(X[:N_TRAIN], label=y[:N_TRAIN])
    return lgb.train(dict(params), ds, params["num_iterations"])


BASE = {"num_leaves": 31, "learning_rate": 0.1, "num_iterations": 30,
        "min_data_in_leaf": 20, "verbosity": -1, "seed": 1}


@pytest.mark.parametrize("case, params, tol", [
    ("binary", {"objective": "binary"}, 3e-3),
    ("binary_options", {"objective": "binary", "bagging_fraction": 0.7,
                        "bagging_freq": 1, "feature_fraction": 0.8,
                        "lambda_l1": 0.5, "lambda_l2": 2.0}, 8e-3),
    ("binary_monotone", {"objective": "binary",
                         "monotone_constraints": "1,-1,0,0,0,0,0,0,0,0,0,0"},
     5e-3),
    # groups keep the generator's X2*X3 interaction within one set, so
    # both implementations can express the signal and the comparison is
    # not dominated by how each routes around a forbidden interaction
    ("interaction", {"objective": "binary",
                     "interaction_constraints":
                         "[0,1],[2,3,4,5,6,7,8,9,10,11]"}, 5e-3),
    ("cegb", {"objective": "binary", "cegb_penalty_split": 0.05,
              "cegb_tradeoff": 0.8}, 8e-3),
    ("maxbin63", {"objective": "binary", "max_bin": 63,
                  "min_gain_to_split": 0.01}, 5e-3),
    # balanced bagging resamples with class-dependent rates (RNG differs
    # across implementations by design)
    ("posneg_bagging", {"objective": "binary", "pos_bagging_fraction": 0.5,
                        "neg_bagging_fraction": 0.9, "bagging_freq": 1},
     1.2e-2),
], ids=lambda v: v if isinstance(v, str) else "")
def test_binary_auc_parity(case, params, tol):
    """Holdout AUC must track the genuine binary within tolerance on the
    same data/params (bagging RNG differs by design, hence wider tol)."""
    full = dict(BASE, **params)
    X, y = _data("binary")
    yva = y[N_TRAIN:]
    ref_raw = _run_reference(X, y, full, X[N_TRAIN:])
    ref_auc = _auc(yva, ref_raw, None, None)
    ours = _run_ours(X, y, full)
    our_auc = _auc(yva, ours.predict(X[N_TRAIN:], raw_score=True),
                   None, None)
    assert abs(our_auc - ref_auc) < tol, (case, our_auc, ref_auc)


@pytest.mark.parametrize("objective, tol", [
    ("regression", 0.03), ("regression_l1", 0.05), ("huber", 0.05)])
def test_regression_rmse_parity(objective, tol):
    """Holdout RMSE ratio vs the genuine binary within tolerance."""
    full = dict(BASE, objective=objective)
    X, y = _data(objective)
    yva = y[N_TRAIN:]
    ref_pred = _run_reference(X, y, full, X[N_TRAIN:])
    ref_rmse = float(np.sqrt(np.mean((yva - ref_pred) ** 2)))
    ours = _run_ours(X, y, full)
    our_rmse = float(np.sqrt(np.mean(
        (yva - ours.predict(X[N_TRAIN:], raw_score=True)) ** 2)))
    assert our_rmse < ref_rmse * (1 + tol), (our_rmse, ref_rmse)


def test_multiclass_accuracy_parity():
    full = dict(BASE, objective="multiclass", num_class=3)
    X, y = _data("multiclass")
    yva = y[N_TRAIN:]
    ref_raw = _run_reference(X, y, full, X[N_TRAIN:])  # (n, 3) raw scores
    ref_acc = (ref_raw.reshape(len(yva), 3).argmax(1) == yva).mean()
    ours = _run_ours(X, y, full)
    our_acc = (ours.predict(X[N_TRAIN:]).argmax(1) == yva).mean()
    assert abs(our_acc - ref_acc) < 5e-3, (our_acc, ref_acc)


def test_quantile_pinball_parity():
    alpha = 0.7
    full = dict(BASE, objective="quantile", alpha=alpha)
    X, y = _data("quantile")
    yva = y[N_TRAIN:]

    def pinball(pred):
        d = yva - pred
        return float(np.mean(np.where(d >= 0, alpha * d, (alpha - 1) * d)))

    ref = pinball(_run_reference(X, y, full, X[N_TRAIN:]))
    ours = _run_ours(X, y, full)
    got = pinball(ours.predict(X[N_TRAIN:], raw_score=True))
    assert got < ref * 1.05, (got, ref)


@pytest.mark.parametrize("objective", ["poisson", "tweedie"])
def test_positive_regression_parity(objective):
    full = dict(BASE, objective=objective)
    rng = np.random.RandomState(SEED)
    n = N_TRAIN + N_VALID
    X = rng.randn(n, 10)
    rate = np.exp(0.5 * X[:, 0] - 0.4 * X[:, 1])
    y = rng.poisson(rate).astype(np.float64)
    yva = y[N_TRAIN:]
    # both emit raw log-rate scores; compare Poisson deviance
    ref_raw = _run_reference(X, y, full, X[N_TRAIN:])
    ours = _run_ours(X, y, full)
    our_raw = ours.predict(X[N_TRAIN:], raw_score=True)

    def dev(raw):
        mu = np.exp(raw)
        return float(np.mean(mu - yva * raw))

    assert dev(our_raw) < dev(ref_raw) * 1.03, (dev(our_raw), dev(ref_raw))


def test_xentropy_parity():
    full = dict(BASE, objective="xentropy")
    X, y = _data("binary")
    y = np.clip(y * 0.8 + 0.1, 0, 1)   # soft labels in [0,1]
    yva = y[N_TRAIN:]

    def ll(raw):
        p = 1 / (1 + np.exp(-raw))
        p = np.clip(p, 1e-15, 1 - 1e-15)
        return float(-np.mean(yva * np.log(p) + (1 - yva) * np.log(1 - p)))

    ref = ll(_run_reference(X, y, full, X[N_TRAIN:]))
    ours = _run_ours(X, y, full)
    got = ll(ours.predict(X[N_TRAIN:], raw_score=True))
    assert got < ref * 1.03, (got, ref)


def test_categorical_feature_parity():
    """Integer categorical columns declared via categorical_feature must
    track the reference's categorical split quality."""
    rng = np.random.RandomState(SEED)
    n = N_TRAIN + N_VALID
    Xnum = rng.randn(n, 6)
    cat1 = rng.randint(0, 12, n)
    cat2 = rng.randint(0, 5, n)
    effect = np.where(np.isin(cat1, [2, 5, 7]), 1.5, -0.5)
    y = (Xnum[:, 0] + effect + 0.4 * (cat2 == 3)
         + 0.3 * rng.randn(n) > 0).astype(float)
    X = np.column_stack([cat1, cat2, Xnum]).astype(np.float64)
    full = dict(BASE, objective="binary", categorical_feature="0,1")
    yva = y[N_TRAIN:]
    ref_auc = _auc(yva, _run_reference(X, y, full, X[N_TRAIN:]), None, None)
    ds = lgb.Dataset(X[:N_TRAIN], label=y[:N_TRAIN],
                     categorical_feature=[0, 1])
    ours = lgb.train({k: v for k, v in full.items()
                      if k != "categorical_feature"}, ds,
                     full["num_iterations"])
    our_auc = _auc(yva, ours.predict(X[N_TRAIN:], raw_score=True),
                   None, None)
    assert abs(our_auc - ref_auc) < 5e-3, (our_auc, ref_auc)


def test_quantized_training_parity():
    """int8-gradient training (use_quantized_grad) quality must track the
    reference's quantized mode."""
    full = dict(BASE, objective="binary", use_quantized_grad="true",
                num_grad_quant_bins=4)
    X, y = _data("binary")
    yva = y[N_TRAIN:]
    ref_auc = _auc(yva, _run_reference(X, y, full, X[N_TRAIN:]), None, None)
    ours = _run_ours(X, y, full)
    our_auc = _auc(yva, ours.predict(X[N_TRAIN:], raw_score=True),
                   None, None)
    assert abs(our_auc - ref_auc) < 8e-3, (our_auc, ref_auc)


@pytest.mark.parametrize("objective, tol", [
    ("lambdarank", 0.02),
    ("rank_xendcg", 0.03),   # stochastic gradients by design — wider band
])
def test_ranking_ndcg_parity(objective, tol):
    """Ranking NDCG@5 vs the genuine binary (query sidecar files)."""
    from lightgbm_tpu.metrics import _ndcg_multi
    rng = np.random.RandomState(SEED)
    n_q, per_q = 1200, 10
    n = n_q * per_q
    X = rng.randn(n, 8)
    rel = X[:, 0] + 0.6 * X[:, 1] + 0.4 * rng.randn(n)
    y = np.zeros(n, np.int64)
    for q in range(n_q):
        sl = slice(q * per_q, (q + 1) * per_q)
        y[sl] = np.minimum(4, np.argsort(np.argsort(rel[sl])) * 5 // per_q)
    n_tr_q = 1000
    ntr = n_tr_q * per_q
    full = dict(BASE, objective=objective, num_iterations=40)
    ref_scores = _run_reference(
        X, y, full, X[ntr:], n_train=ntr,
        query=(np.full(n_tr_q, per_q), np.full(n_q - n_tr_q, per_q)))
    ds = lgb.Dataset(X[:ntr], label=y[:ntr], group=np.full(n_tr_q, per_q))
    ours = lgb.train(full, ds, full["num_iterations"])
    gains = np.array([(1 << i) - 1 for i in range(32)], np.float64)
    va_group = np.full(n_q - n_tr_q, per_q)

    def ndcg5(scores):
        return _ndcg_multi(y[ntr:], scores, va_group, (5,), gains)[0]

    assert abs(ndcg5(ours.predict(X[ntr:], raw_score=True))
               - ndcg5(ref_scores)) < tol


def test_linear_tree_parity():
    """linear_tree leaves fit per-leaf linear models (Eigen in the
    reference, normal equations here); holdout RMSE must track."""
    full = dict(BASE, objective="regression", linear_tree="true",
                linear_lambda=0.01)
    X, y = _data("regression")
    yva = y[N_TRAIN:]
    ref_pred = _run_reference(X, y, full, X[N_TRAIN:])
    ref_rmse = float(np.sqrt(np.mean((yva - ref_pred) ** 2)))
    ours = _run_ours(X, y, full)
    our_rmse = float(np.sqrt(np.mean(
        (yva - ours.predict(X[N_TRAIN:], raw_score=True)) ** 2)))
    assert our_rmse < ref_rmse * 1.05, (our_rmse, ref_rmse)


@pytest.mark.parametrize("case, extra, tol", [
    ("goss", {"data_sample_strategy": "goss"}, 1e-2),
    ("dart", {"boosting": "dart", "drop_rate": 0.1}, 1.5e-2),
    ("extra_path_smooth", {"extra_trees": "true", "path_smooth": 1.0,
                           "max_depth": 8}, 1.5e-2),
])
def test_stochastic_mode_auc_parity(case, extra, tol):
    """Sampling/drop RNG differs across implementations by design; the
    holdout AUC of each mode must still land in the same band."""
    full = dict(BASE, objective="binary", **extra)
    X, y = _data("binary")
    yva = y[N_TRAIN:]
    ref_auc = _auc(yva, _run_reference(X, y, full, X[N_TRAIN:]), None, None)
    ours = _run_ours(X, y, full)
    our_auc = _auc(yva, ours.predict(X[N_TRAIN:], raw_score=True),
                   None, None)
    assert abs(our_auc - ref_auc) < tol, (case, our_auc, ref_auc)


def test_weighted_binary_parity():
    """Sample weights flow through gradients, hessians, min_sum_hessian
    and boost-from-average; weighted AUC must track the reference."""
    full = dict(BASE, objective="binary")
    X, y = _data("binary")
    rng = np.random.RandomState(3)
    w = np.exp(rng.randn(len(y)) * 0.5)
    yva, wva = y[N_TRAIN:], w[N_TRAIN:]
    ref_raw = _run_reference(X, y, full, X[N_TRAIN:], weight=w)
    ref_auc = _auc(yva, ref_raw, wva, None)
    ds = lgb.Dataset(X[:N_TRAIN], label=y[:N_TRAIN], weight=w[:N_TRAIN])
    ours = lgb.train(dict(full), ds, full["num_iterations"])
    our_auc = _auc(yva, ours.predict(X[N_TRAIN:], raw_score=True), wva, None)
    assert abs(our_auc - ref_auc) < 5e-3, (our_auc, ref_auc)


def test_leaf_and_contrib_prediction_parity():
    """Load OUR model file into the genuine binary and compare leaf-index
    and SHAP-contribution predictions element-wise — same model, so
    traversal and TreeSHAP must agree exactly (not just in quality)."""
    full = dict(BASE, objective="binary", num_iterations=12)
    X, y = _data("binary")
    Xva = X[N_TRAIN:N_TRAIN + 500]
    ours = _run_ours(X, y, full)

    d = tempfile.mkdtemp()
    try:
        ours.save_model(f"{d}/m.txt")
        np.savetxt(f"{d}/va.csv",
                   np.column_stack([np.zeros(len(Xva)), Xva]),
                   delimiter=",", fmt="%.17g")
        for mode, flag in [("leaf", "predict_leaf_index"),
                           ("contrib", "predict_contrib")]:
            with open(f"{d}/{mode}.conf", "w") as fh:
                fh.write(f"task = predict\ndata = {d}/va.csv\n"
                         f"input_model = {d}/m.txt\n"
                         f"output_result = {d}/{mode}.txt\n"
                         f"{flag} = true\n")
            _cli(f"{d}/{mode}.conf")
        ref_leaf = np.loadtxt(f"{d}/leaf.txt")
        ref_contrib = np.loadtxt(f"{d}/contrib.txt")
    finally:
        shutil.rmtree(d, ignore_errors=True)

    our_leaf = ours.predict(Xva, pred_leaf=True)
    np.testing.assert_array_equal(our_leaf, ref_leaf)
    our_contrib = ours.predict(Xva, pred_contrib=True)
    np.testing.assert_allclose(our_contrib, ref_contrib,
                               rtol=1e-5, atol=1e-6)


def test_forced_splits_parity(tmp_path):
    """forcedsplits_filename pins the tree's top splits on both sides; the
    forced structure plus learned remainder must match in quality."""
    import json as _json
    spec = {"feature": 0, "threshold": 0.0,
            "left": {"feature": 1, "threshold": -0.5}}
    fs = tmp_path / "forced.json"
    fs.write_text(_json.dumps(spec))
    full = dict(BASE, objective="binary", forcedsplits_filename=str(fs))
    X, y = _data("binary")
    yva = y[N_TRAIN:]
    ref_auc = _auc(yva, _run_reference(X, y, full, X[N_TRAIN:]), None, None)
    ours = _run_ours(X, y, full)
    our_auc = _auc(yva, ours.predict(X[N_TRAIN:], raw_score=True),
                   None, None)
    assert abs(our_auc - ref_auc) < 5e-3, (our_auc, ref_auc)


def test_weight_column_cli_parity(tmp_path):
    """weight_column=<idx> in-data weights through BOTH CLIs: ours and the
    genuine binary must produce matching weighted-AUC on the holdout."""
    import subprocess as sp
    X, y = _data("binary")
    rng = np.random.RandomState(5)
    w = np.exp(rng.randn(len(y)) * 0.5)
    yva, wva = y[N_TRAIN:], w[N_TRAIN:]
    full = dict(BASE, objective="binary", weight_column="0")

    def run_cli(cmd_prefix, out_model):
        tr = tmp_path / f"{out_model}_tr.csv"
        va = tmp_path / f"{out_model}_va.csv"
        # file columns: label, weight, features  (weight_column=0 in
        # X-space = first post-label column)
        np.savetxt(tr, np.column_stack([y[:N_TRAIN], w[:N_TRAIN],
                                        X[:N_TRAIN]]),
                   delimiter=",", fmt="%.17g")
        np.savetxt(va, np.column_stack([np.zeros(N_VALID), w[N_TRAIN:],
                                        X[N_TRAIN:]]),
                   delimiter=",", fmt="%.17g")
        conf = tmp_path / f"{out_model}.conf"
        conf.write_text("".join(f"{k} = {v}\n" for k, v in full.items())
                        + f"data = {tr}\noutput_model = "
                        f"{tmp_path}/{out_model}.txt\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = sp.run([*cmd_prefix, f"config={conf}"], capture_output=True,
                   text=True, env=env)
        assert r.returncode == 0, r.stderr[-1500:]
        pconf = tmp_path / f"{out_model}_p.conf"
        pconf.write_text(
            f"task = predict\ndata = {va}\ninput_model = "
            f"{tmp_path}/{out_model}.txt\noutput_result = "
            f"{tmp_path}/{out_model}_p.txt\npredict_raw_score = true\n"
            f"weight_column = 0\nlabel_column = 0\n")
        r = sp.run([*cmd_prefix, f"config={pconf}"], capture_output=True,
                   text=True, env=env)
        assert r.returncode == 0, r.stderr[-1500:]
        return np.loadtxt(f"{tmp_path}/{out_model}_p.txt")

    ref_raw = run_cli([BIN], "ref")
    import sys
    ours_raw = run_cli([sys.executable, "-m", "lightgbm_tpu"], "ours")
    ref_auc = _auc(yva, ref_raw, wva, None)
    our_auc = _auc(yva, ours_raw, wva, None)
    assert abs(our_auc - ref_auc) < 5e-3, (our_auc, ref_auc)
