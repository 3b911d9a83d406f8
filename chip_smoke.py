"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of the headline model (Higgs shape: 1 000 000 x 28,
255 leaves, ``max_bin=255``, ``tpu_leaf_batch=16``; data and weights from a
seed, depth cut to 8 + 4 boosting rounds):

    bin -> lgb.train fp32 -> lgb.train quantized -> serve.Predictor (int8
    fused traversal + fp32) -> save/load -> [>= 4 devices: tree_learner=data]

and asserts WHAT RAN, not what was asked: the Pallas histogram, the fused
wave kernel and the fused traversal kernel, compiled by Mosaic (interpret
off), agreeing with the plain XLA path of the same repo.  Nothing here is a
benchmark: the wall seconds it prints are smoke timings, and the summary's
``claim`` is null.  The last line of stdout is the verdict and nothing else,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as jax reports it; the ``summary:`` line before it has the facts.

It needs a TPU.  Without one — or in a directory that holds only this file
— it exits non-zero and prints no result.  ``--dry-run`` is the one
exception, for debugging the script itself before chip time is spent: a
tiny-size CPU rehearsal with the kernels in interpret mode, whose output
says ``platform: cpu``.

No ``try``/``except`` wraps a phase: the first failed check ends the run.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

T0 = time.time()
DRY_RUN = "--dry-run" in sys.argv[1:]
if [a for a in sys.argv[1:] if a != "--dry-run"]:
    sys.exit("usage: python chip_smoke.py [--dry-run]")

# ---- ask jax for the TPU by name, before jax is imported ------------------
if DRY_RUN:
    # four virtual CPU devices, so the multichip section rehearses too
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
else:
    asked = os.environ.get("JAX_PLATFORMS", "")
    if not asked:
        os.environ["JAX_PLATFORMS"] = "tpu"
    elif asked.split(",")[0].strip().lower() != "tpu":
        sys.exit(f"chip_smoke: JAX_PLATFORMS={asked!r} does not put the TPU "
                 "first; this script only runs on a TPU (or, for a CPU "
                 "rehearsal of the script itself, with --dry-run)")

import numpy as np  # noqa: E402

import jax  # noqa: E402


def check(cond, what: str) -> None:
    """A smoke assertion that survives ``python -O``."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED — {what}")


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


_devices = jax.devices()           # no accelerator -> raises -> non-zero exit
DEVICE = {"platform": _devices[0].platform,
          "kind": _devices[0].device_kind, "count": len(_devices)}
check(DEVICE["platform"] == ("cpu" if DRY_RUN else "tpu"),
      f"jax resolved platform {DEVICE['platform']!r}")
TIMINGS = {"backend_init": round(time.time() - T0, 3)}

# the repo (absent when this file sits alone: ImportError, non-zero exit)
import bench  # noqa: E402
import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import native, serve  # noqa: E402
from lightgbm_tpu.metrics import _auc  # noqa: E402
from lightgbm_tpu.ops.pallas_common import interpret_mode  # noqa: E402
from lightgbm_tpu.utils.jax_cache import (cache_entry_count,  # noqa: E402
                                          enable_compile_cache)

# ---- sizes ----------------------------------------------------------------
FEATURES = bench.FEATURES                       # 28: the model's full width
if DRY_RUN:
    ROWS, LEAVES, FP32_ROUNDS, QUANT_ROUNDS = 4 * 2560, 15, 2, 2
    AGREE_ROWS, REQUESTS = 4096, (1, 64, 256)
else:
    ROWS, LEAVES, FP32_ROUNDS, QUANT_ROUNDS = 1_000_000, bench.NUM_LEAVES, 8, 4
    AGREE_ROWS, REQUESTS = 100_000, (1, 256, 8192)

# The plain (un-kerneled) path the kernels are checked against.  On the
# chip that is the XLA one-hot histogram: the scatter-add `segment` impl
# plans 14.6 GiB of temporaries at 1 M rows (XLA memory analysis of the
# compiled v5e program), which a 16 GB chip cannot hold.
PLAIN_IMPL = "segment" if DRY_RUN else "onehot"

# ---- compile seconds, from jax's own monitoring events --------------------
_compile_s = [0.0]


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += seconds     # a persistent-cache load counts here too


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def timed(fn):
    """(result, wall seconds, of which compile-or-cache-load seconds)."""
    c0, t0 = _compile_s[0], time.time()
    out = fn()
    return out, time.time() - t0, _compile_s[0] - c0


def versions() -> dict:
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def train(params: dict, ds, rounds: int):
    bst = lgb.train(params, ds, num_boost_round=rounds)
    jax.block_until_ready(bst._gbdt.scores)
    return bst


def trees_of(bst):
    return bst._gbdt.host_trees()[0]


_STRUCTURE = ("split_feature", "split_bin", "default_left", "left_child",
              "right_child")


def agree(a, b, what: str, Xs, ys, *, gain_tol: float, auc_tol: float) -> dict:
    """Two runs of the same data through two paths of the repo must be the
    same model up to what their arithmetic may differ by.  In order:

    - ``identical``: every tree bitwise equal (structure, gains, leaves);
    - ``same_splits``: identical structure; gains within ``gain_tol`` of
      the tree's largest gain, leaf values within rtol 1e-4 / atol 1e-6
      (the tolerance tests/test_parallel.py pins for this situation);
    - ``near_tie``: up to the first split the runs chose differently both
      grew from identical state, so that split's two gains are what the
      paths' rounding made of one near-tie — they must agree within
      ``gain_tol`` (relative), and the runs' AUC within ``auc_tol``.

    Anything else fails the smoke."""
    ta, tb = trees_of(a), trees_of(b)
    check(len(ta) == len(tb), f"{what}: {len(ta)} vs {len(tb)} trees")
    out = {"verdict": "identical"}
    for ti, (x, y) in enumerate(zip(ta, tb)):
        m = min(x.num_leaves, y.num_leaves) - 1
        differ = np.flatnonzero(
            (x.split_feature[:m] != y.split_feature[:m])
            | (x.split_bin[:m] != y.split_bin[:m]))
        if differ.size:
            node = int(differ[0])
            ga, gb = float(x.split_gain[node]), float(y.split_gain[node])
            rel = abs(ga - gb) / max(abs(ga), abs(gb), 1e-30)
            auc_a = _auc(ys, a.predict(Xs, raw_score=True), None, None)
            auc_b = _auc(ys, b.predict(Xs, raw_score=True), None, None)
            say(f"{what}.first_differing_split",
                f"tree {ti} node {node}: feature {x.split_feature[node]}/"
                f"{y.split_feature[node]} bin {x.split_bin[node]}/"
                f"{y.split_bin[node]} gains {ga!r} vs {gb!r} (rel {rel:.2e}, "
                f"tol {gain_tol:.0e}); auc {auc_a:.6f} vs {auc_b:.6f} "
                f"(tol {auc_tol:.0e})")
            check(rel <= gain_tol, f"{what}: gains {ga} vs {gb} at the first "
                  f"differing split (tree {ti} node {node}) are no near-tie")
            check(abs(auc_a - auc_b) <= auc_tol,
                  f"{what}: AUC {auc_a} vs {auc_b}")
            out = {"verdict": "near_tie", "tree": ti, "node": node,
                   "gain_rel_diff": rel, "auc_abs_diff": abs(auc_a - auc_b)}
            break
        check(x.num_leaves == y.num_leaves and all(
            np.array_equal(getattr(x, f), getattr(y, f)) for f in _STRUCTURE),
            f"{what}: tree {ti} differs in structure past identical splits")
        if not (np.array_equal(x.split_gain, y.split_gain)
                and np.array_equal(x.leaf_value, y.leaf_value)):
            gd = float(np.abs(x.split_gain - y.split_gain).max()
                       / max(np.abs(x.split_gain).max(), 1e-30))
            check(gd <= gain_tol, f"{what}: tree {ti} gains differ by {gd} "
                  "of the largest gain")
            check(np.allclose(x.leaf_value, y.leaf_value, rtol=1e-4,
                              atol=1e-6), f"{what}: tree {ti} leaf values")
            out = {"verdict": "same_splits",
                   "max_gain_diff": max(gd, out.get("max_gain_diff", 0.0))}
    say(f"{what}.agreement", out)
    return out


def assert_default_path(bst, what: str) -> dict:
    """What ran, not what was asked."""
    plan = bst._gbdt.plan
    leaves = int(trees_of(bst)[0].num_leaves)
    say(f"{what}.plan", str(plan))
    say(f"{what}.first_tree_leaves", leaves)
    check(plan.hist_impl == "pallas",
          f"{what}: histogram impl resolved to {plan.hist_impl!r}")
    check(plan.body == "wave" and plan.fused,
          f"{what}: fused wave kernel inactive: {plan.why.get('fused')}")
    check(leaves > LEAVES // 2,
          f"{what}: first tree has {leaves} of {LEAVES} leaves")
    check(bool(np.isfinite(np.asarray(bst._gbdt.scores)).all()),
          f"{what}: non-finite training scores")
    return {"histogram_impl": plan.hist_impl, "wave_fused_active": True,
            "first_tree_leaves": leaves, "trees": len(trees_of(bst))}


def main() -> int:
    say("platform", DEVICE["platform"])
    say("device_kind", DEVICE["kind"])
    say("device_count", DEVICE["count"])
    vers = versions()
    say("versions", vers)
    say("dry_run", DRY_RUN)
    interp = interpret_mode()
    say("pallas_interpret_mode", interp)
    check(interp is DRY_RUN, "interpret mode must be off on the chip")
    check(native.available(), "native host library did not build/load")
    say("native_library", "loaded")

    cache_dir = enable_compile_cache()
    entries_before = cache_entry_count(cache_dir)
    say("compile_cache", f"{cache_dir} ({entries_before} entries)")

    facts = {"interpret_mode": interp, "native_library": True,
             "plain_impl": PLAIN_IMPL}

    # ---- data from a seed, binned once ------------------------------------
    (X, y), TIMINGS["data"], _ = timed(
        lambda: bench.make_higgs_like(ROWS, FEATURES))
    params = dict(bench.bench_params(), num_leaves=LEAVES)
    if DRY_RUN:
        # `auto` resolves to the XLA path on a CPU backend; the rehearsal
        # names the kernels so their bodies run (interpreted)
        params.update(tpu_histogram_impl="pallas", tpu_wave_kernel="fused")
    default_impl = params.get("tpu_histogram_impl", "auto")
    plain = {"tpu_histogram_impl": PLAIN_IMPL, "tpu_wave_kernel": "unfused"}
    ds = lgb.Dataset(X, label=y)
    binned, TIMINGS["binning"], _ = timed(
        lambda: ds.construct(params).binned)
    Xs, ys = X[:AGREE_ROWS], y[:AGREE_ROWS]

    # ---- the f32 histogram kernel against an f64 reference ----------------
    # (the one fp32 check that is not statistical: measured on the v5e the
    # kernel lands within 1e-6 of max|hist| at 262 k rows, 8e-6 at 1 M)
    from lightgbm_tpu.ops.histogram import histogram_from_vals
    hbins = np.asarray(binned.bins[:AGREE_ROWS])
    nbins = int(binned.max_num_bins)
    hrng = np.random.RandomState(1)
    hvals = np.stack([hrng.randn(AGREE_ROWS) * 0.3,
                      hrng.rand(AGREE_ROWS) * 0.25,
                      np.ones(AGREE_ROWS)], 1).astype(np.float32)
    got = np.asarray(histogram_from_vals(
        jax.numpy.asarray(hbins), jax.numpy.asarray(hvals), num_bins=nbins,
        impl=default_impl), np.float64)
    want = np.stack([np.stack([np.bincount(
        hbins[:, j], weights=hvals[:, c].astype(np.float64),
        minlength=nbins)[:nbins] for c in range(3)], -1)
        for j in range(FEATURES)])
    herr = float((np.abs(got - want).max(axis=(0, 1))
                  / np.abs(want).max(axis=(0, 1))).max())
    say("kernel.f32_histogram_vs_f64.max_err_over_max_hist",
        f"{herr:.3e} (tol 1e-05)")
    check(herr <= 1e-5, f"f32 histogram kernel off its f64 reference: {herr}")
    facts["f32_histogram_err_vs_f64"] = herr

    # ---- fp32: default path, then the plain path it must agree with -------
    bst, wall, comp = timed(lambda: train(params, ds, FP32_ROUNDS))
    TIMINGS["first_step_compile"] = comp
    TIMINGS["steady_rounds"] = wall - comp      # trace + FP32_ROUNDS rounds
    facts["fp32"] = assert_default_path(bst, "fp32")
    ref, TIMINGS["fp32_plain_train"], _ = timed(
        lambda: train(dict(params, **plain), ds, FP32_ROUNDS))
    check(not ref._gbdt.plan.fused, "plain path ran the fused kernel")
    p_def = bst.predict(Xs, raw_score=True)
    gap = np.abs(p_def - ref.predict(Xs, raw_score=True))
    say("fp32.vs_plain.raw_score_gap",
        f"mean {gap.mean():.3e} max {gap.max():.3e} over {AGREE_ROWS} rows")
    # f32 histogram sums group differently in the kernel (1024-row blocks,
    # MXU passes) and the XLA scan (16384-row blocks): measured on the v5e,
    # each is within 1e-5 of max|hist| of an f64 reference at 1 M rows, and
    # a split's gain — a difference of large terms — moves by ~1e-4
    # relative.  So a near-tie may flip, after which the two trees are
    # different-but-equivalent.  Stated tolerance: first differing split's
    # gains within 1e-3 relative, AUC within 2e-3.
    facts["fp32"]["vs_plain"] = agree(bst, ref, "fp32.default_vs_plain",
                                      Xs, ys, gain_tol=1e-3, auc_tol=2e-3)
    facts["fp32"]["vs_plain"].update(raw_gap_mean=float(gap.mean()),
                                     raw_gap_max=float(gap.max()))

    # ---- quantized: integer histograms, so agreement is identity ----------
    qparams = dict(params, use_quantized_grad=True)
    qbst, TIMINGS["quant_train"], _ = timed(
        lambda: train(qparams, ds, QUANT_ROUNDS))
    facts["quantized"] = assert_default_path(qbst, "quantized")
    q_unf, TIMINGS["quant_pallas_unfused_train"], _ = timed(
        lambda: train(dict(qparams, tpu_histogram_impl=default_impl,
                           tpu_wave_kernel="unfused"), ds, QUANT_ROUNDS))
    check(q_unf._gbdt.plan.hist_impl == "pallas"
          and not q_unf._gbdt.plan.fused,
          "unfused pallas run did not run the unfused pallas path")
    q_plain, TIMINGS["quant_plain_train"], _ = timed(
        lambda: train(dict(qparams, **plain), ds, QUANT_ROUNDS))
    # (a) the Pallas histogram vs the plain XLA histogram, both feeding the
    # XLA split scan; (b) the fused wave kernel vs (a).  int32 histogram
    # sums are exact, so the paths can differ only in how the f32 scan was
    # compiled: 1e-5 on gains, 1e-4 on AUC.
    facts["quantized"]["pallas_unfused_vs_plain"] = agree(
        q_unf, q_plain, "quantized.pallas_unfused_vs_plain", Xs, ys,
        gain_tol=1e-5, auc_tol=1e-4)
    facts["quantized"]["fused_vs_unfused"] = agree(
        qbst, q_unf, "quantized.fused_vs_unfused", Xs, ys,
        gain_tol=1e-5, auc_tol=1e-4)

    # ---- serve -------------------------------------------------------------
    traverse = "fused" if DRY_RUN else None     # None = the booster's auto
    p_int8 = serve.Predictor(bst, raw_score=True, quantize="int8",
                             traverse=traverse, host_fallback=False)
    p_fp32 = bst.serving_predictor(raw_score=True, host_fallback=False)
    say("serve.int8.traverse_mode", p_int8.plan.traverse_mode)
    check(p_int8.plan.traverse_mode == "fused",
          f"int8 pack traverses {p_int8.plan.traverse_mode!r} "
          f"({p_int8.plan.traverse_degrade})")
    check(p_int8.plan._interpret is interp, "serve plan interpret mode "
          "disagrees with the one decision function")
    (_, TIMINGS["serve_warmup"], _) = timed(
        lambda: (p_int8.warmup(max(REQUESTS)), p_fp32.warmup(max(REQUESTS))))
    bound = p_int8.plan.quantize_error_bound()
    t_req = time.time()
    for n in REQUESTS:
        want = bst.predict(X[:n], raw_score=True)
        for name, pred, tol in (("int8", p_int8, bound + 1e-6),
                                ("fp32", p_fp32, 1e-6)):
            got = pred.predict(X[:n])
            check(got.shape == want.shape and bool(np.isfinite(got).all()),
                  f"serve {name} n={n}: shape {got.shape} / non-finite")
            err = float(np.abs(got - want).max())
            say(f"serve.{name}.rows_{n}.max_err", f"{err:.3e} (tol {tol:.3e})")
            check(err <= tol, f"serve {name} n={n}: |err| {err} > {tol}")
    TIMINGS["serve_requests"] = time.time() - t_req
    for name, pred in (("int8", p_int8), ("fp32", p_fp32)):
        snap = pred.metrics_snapshot()
        counts = {k: snap[k] for k in ("device_faults", "host_fallbacks",
                                       "nan_scores")}
        say(f"serve.{name}.counters", counts)
        check(not any(counts.values()), f"serve {name}: {counts}")
    facts["serve"] = {"int8_traverse_mode": "fused",
                      "int8_error_bound": bound, "device_faults": 0,
                      "host_fallbacks": 0, "nan_scores": 0}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.txt")
        bst.save_model(path)
        back = lgb.Booster(model_file=path).predict(Xs[:1000], raw_score=True)
    err = float(np.abs(back - p_def[:1000]).max())
    say("save_load_predict.max_err", f"{err:.3e}")
    check(err <= 1e-6, f"save -> load -> predict round trip: |err| {err}")

    # ---- four chips: tree_learner=data over all of them -------------------
    if DEVICE["count"] >= 4:
        mbst, TIMINGS["multichip_train"], _ = timed(
            lambda: train(dict(qparams, tree_learner="data",
                               tpu_wave_kernel="auto"), ds, QUANT_ROUNDS))
        g = mbst._gbdt
        homes = {s.device for s in g.bins_dev.addressable_shards}
        say("multichip.bins_shard_devices", len(homes))
        say("multichip.plan", str(g.plan))
        check(len(homes) == DEVICE["count"], "bins_dev is not sharded over "
              f"all {DEVICE['count']} devices: {sorted(map(str, homes))}")
        check(g.plan.reduce == "scatter",
              "reduce-scatter inactive on the data mesh: "
              f"{g.plan.why.get('scatter')}")
        check(g.plan.hist_impl == "pallas",
              "multichip run did not use the Pallas histogram")
        # sharded vs serial from the same int8 gradients: the identity
        # tests/test_parallel.py pins (same splits, leaf values rtol 1e-4),
        # else AUC within 1e-3
        pin = ("tests/test_parallel.py::"
               "test_sharded_perm_grower_matches_serial_exactly")
        say("multichip.identity", pin)
        facts["multichip"] = {
            "devices": DEVICE["count"], "rs_active": True, "identity": pin,
            "vs_one_chip_unfused": agree(
                mbst, q_unf, "multichip.vs_one_chip_unfused", Xs, ys,
                gain_tol=1e-5, auc_tol=1e-3)}
    else:
        facts["multichip"] = f"skipped ({DEVICE['count']} device)"
        say("multichip", facts["multichip"])

    entries_after = cache_entry_count(cache_dir)
    TIMINGS["total"] = time.time() - T0
    # the facts, as one JSON object on the line before last ...
    say("summary", json.dumps({
        "dry_run": DRY_RUN, "versions": vers,
        "rows": ROWS, "features": FEATURES, "num_leaves": LEAVES,
        "rounds": {"fp32": FP32_ROUNDS, "quantized": QUANT_ROUNDS},
        **facts,
        "compile_cache": {"dir": cache_dir, "entries_before": entries_before,
                          "entries_after": entries_after},
        "smoke_timings_s": {k: round(v, 3) for k, v in TIMINGS.items()},
        "claim": None}))
    # ... and the verdict, reached only if every check above held: the last
    # line of stdout is exactly {"ok", "device": {"platform","kind","count"}}
    print(json.dumps({"ok": True, "device": DEVICE}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
