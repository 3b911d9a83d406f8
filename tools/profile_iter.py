"""Capture a jax.profiler trace of ONE bench-config training iteration and
print the top time sinks (VERDICT r4 ask #1: if vs_baseline < 1.0, name
the top-3 sinks in PERF.md), plus a host-sync census: device_get calls per
boosting iteration on the per-round path vs the iteration-packed path
(docs/ITER_PACK.md), so the pack path's dispatch-elimination claim is
measurable outside bench.py — and a NON-FUSED-path census
(:func:`nonfused_dispatch_census`): the GOSS / CEGB / linear_tree configs
route through ``gbdt.train_one_iter``'s ``used_fused=False`` branch, whose
per-iteration dispatch and host-sync counts were previously invisible in
profiles (the fused-path coverage gap, ISSUE-4 satellite).

    python tools/profile_iter.py [rows] [iters]

Writes the trace to /tmp/tpu_trace (open with tensorboard or xprof) and
prints a coarse wall-clock breakdown measured around the device fences.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Compiled training-program entry points on the GBDT instance (all
# dynamically attribute-resolved at call time, so wrapping the attribute
# intercepts every launch): the fused iteration, the grow+apply program,
# the bare grower, and the objective-gradient program.
_DISPATCH_ATTRS = ("_fused_iter", "_grow_apply", "grow", "_grad_fn",
                   "_goss_sample")


def _count_dispatches_and_syncs(bst, iters):
    """Run ``iters`` post-warmup boosting rounds counting (a) launches of
    the GBDT's compiled training programs and (b) jax.device_get host
    syncs.  The dispatch census counts the big jitted programs (grower /
    gradients / score update / GOSS mask), not ad-hoc eager ops — the
    quantity comparable to bench.py's ``dispatches_per_iter`` (1.0 on the
    fused path)."""
    import jax

    import lightgbm_tpu.models.gbdt as gbdt_mod

    gbdt = bst._gbdt
    # compile outside the census: one round, and where GOSS leaves its
    # first int(1 / learning_rate) rounds unsampled, the first sampled one
    strategy = gbdt.sample_strategy
    for _ in range(1 + (strategy.goss_unsampled_iters if strategy.is_goss
                        else 0)):
        bst.update()
    counts = {"dispatch": 0, "sync": 0}
    wrapped = []

    def wrap(obj, name):
        fn = getattr(obj, name, None)
        if fn is None or not callable(fn):
            return

        def counting(*a, __fn=fn, **k):
            counts["dispatch"] += 1
            return __fn(*a, **k)

        setattr(obj, name, counting)
        wrapped.append((obj, name, fn))

    import lightgbm_tpu.ops.linear as linear_ops_mod

    for name in _DISPATCH_ATTRS:
        wrap(gbdt, name)
    for name in ("_add_leaf_outputs", "_scale_tree_arrays",
                 "_mark_features_used"):
        wrap(gbdt_mod, name)
    wrap(linear_ops_mod, "fit_linear_leaves_device")
    orig_get = jax.device_get

    def counting_get(x):
        counts["sync"] += 1
        return orig_get(x)

    jax.device_get = counting_get
    try:
        for _ in range(iters):
            bst.update()
    finally:
        jax.device_get = orig_get
        for obj, name, fn in wrapped:
            setattr(obj, name, fn)
    return counts["dispatch"], counts["sync"]


_CENSUS_PATHS = (
    ("fused", {}),
    # learning_rate 1: GOSS leaves only the first int(1 / learning_rate)
    # = 1 iteration unsampled, so a few census iterations reach the sampler
    ("goss", {"data_sample_strategy": "goss", "learning_rate": 1.0}),
    ("goss_host", {"data_sample_strategy": "goss", "learning_rate": 1.0,
                   "tpu_device_goss": "off"}),
    ("cegb", {"cegb_penalty_split": 0.1,
              "cegb_penalty_feature_coupled": [1.0] * 8}),
    ("linear_tree", {"linear_tree": True}),
)


def nonfused_dispatch_census(rows=8192, iters=4, num_leaves=31,
                             paths=None):
    """Per-iteration dispatch/host-sync counts for the bench config's hot
    path and the sampling/penalty variants.  Since ISSUE-5, GOSS
    (tpu_device_goss auto/on) and CEGB ride the fused ONE-dispatch
    iteration (``used_fused=True``, 1.0 dispatches/iter); the remaining
    ``used_fused=False`` fallbacks are the host GOSS sampler
    (tpu_device_goss=off) and linear trees — whose leaf models now solve
    in one batched device dispatch, so their host-sync count is a small
    CONSTANT independent of num_leaves (0 per-leaf syncs; run this
    census at two leaf counts to witness it).  Returns one blob per
    path."""
    import numpy as np

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    X = rng.randn(rows, 8)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    base = {"objective": "binary", "num_leaves": num_leaves,
            "metric": "none", "verbosity": -1}
    out = []
    for name, extra in _CENSUS_PATHS:
        if paths is not None and name not in paths:
            continue
        ds = lgb.Dataset(X, label=y)
        bst = lgb.Booster(params=dict(base, **extra), train_set=ds)
        g = bst._gbdt
        dispatches, syncs = _count_dispatches_and_syncs(bst, iters)
        out.append({
            "path": name,
            "used_fused": g.fused_path_active,
            "num_leaves": num_leaves,
            "dispatches_per_iter": round(dispatches / iters, 2),
            "host_syncs_per_iter": round(syncs / iters, 2),
        })
    return out


def _train_step_compiled(bst):
    """AOT-compile the booster's grower program (the train step's dominant
    dispatch) and return the compiled object — memoized per GBDT so the
    cost-analysis and memory-analysis blocks in one bench blob share ONE
    compile instead of paying it twice."""
    import jax  # noqa: F401 — backend must be up for lower()
    import jax.numpy as jnp

    g = bst._gbdt
    cached = getattr(g, "_profile_train_step_compiled", None)
    if cached is not None:
        return cached
    n = g.train_data.num_data
    f = g.train_data.num_features
    meta = g.meta_dev
    args = [g.bins_dev, jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32),
            jnp.ones(n, jnp.float32), jnp.ones(f, bool),
            meta["num_bins_per_feature"], meta["nan_bins"],
            meta["is_categorical"], meta["monotone"]]
    if g._fg_dev is not None:
        # EFB: the grower needs the bundle maps (positional tail)
        args += [None, None, None, None, g._fg_dev, g._fo_dev]
    t0 = time.perf_counter()
    compiled = g.grow.lower(*args).compile()
    # This AOT path is the one caller holding the compiled object, so its
    # compile.end event carries the memory_analysis byte summary the jit
    # seam cannot produce (telemetry/memory.py note_compile).
    from lightgbm_tpu.telemetry.memory import note_compile
    note_compile("profile/train_step", time.perf_counter() - t0,
                 compiled=compiled)
    g._profile_train_step_compiled = compiled
    return compiled


def train_step_hlo_cost(bst):
    """XLA's own cost model for the booster's compiled grower program (the
    train step's dominant dispatch): ``compiled.cost_analysis()`` FLOPs /
    bytes-accessed, AOT-lowered on whatever backend is live — the
    platform-independent compile-time cost number every kernel PR lands
    with even when the TPU probe verdict is not live (ROADMAP 3b; the
    ``detail.hlo_cost`` block in every BENCH json)."""
    cost = _train_step_compiled(bst).cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    out = {}
    for k_out, k_in in (("flops", "flops"),
                        ("bytes_accessed", "bytes accessed"),
                        ("transcendentals", "transcendentals")):
        v = cost.get(k_in)
        if v is not None:
            out[k_out] = float(v)
    return out


def train_step_memory_analysis(bst):
    """XLA's compiled memory plan for the same grower program
    (``compiled.memory_analysis()``): temp / generated-code / argument /
    output / donated-alias bytes — the compile-time half of the
    ``detail.memory`` block (ISSUE-10), sharing :func:`_train_step_compiled`'s
    one AOT compile with the cost block above."""
    from lightgbm_tpu.telemetry.memory import memory_analysis_summary
    out = memory_analysis_summary(_train_step_compiled(bst))
    if out is None:
        return {"unavailable": True}
    return out


def fused_wave_census(rows=4096, features=12, num_leaves=15, leaf_batch=4):
    """Histogram-kernel dispatches per WAVE, fused vs unfused (ISSUE-7):
    the unfused wave body issues one histogram call per leaf (a W-trip
    ``fori_loop`` over the bucket switch), the fused kernel issues ONE
    ``pallas_call`` per wave with leaf batches pipelined through the grid.
    ``hist_dispatches_per_wave`` is derived from the grower's own declared
    dispatch structure (the growth plan, ``GBDT.plan.fused`` — the
    SAME plan the trace is built from, so the census cannot disagree
    with the program), and each blob carries the measured program
    dispatches/iter so the fused kernel is witnessed not to add launches.
    On CPU the fused grower runs the kernel body in interpret mode — the
    census doubles as tier-1 coverage of the fused trace."""
    import numpy as np

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    X = rng.randn(rows, features)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    out = []
    for mode in ("fused", "unfused"):
        ds = lgb.Dataset(X, label=y)
        bst = lgb.Booster(params={"objective": "binary",
                                  "num_leaves": num_leaves,
                                  "tpu_leaf_batch": leaf_batch,
                                  "metric": "none", "verbosity": -1,
                                  "tpu_wave_kernel": mode}, train_set=ds)
        g = bst._gbdt
        active = bool(g.wave_fused_active)
        dispatches, syncs = _count_dispatches_and_syncs(bst, 2)
        out.append({
            "wave_kernel": mode,
            "fused_active": active,
            "leaf_batch": int(g.grower_cfg.leaf_batch),
            "hist_dispatches_per_wave": (
                1 if active else int(g.grower_cfg.leaf_batch)),
            "dispatches_per_iter": round(dispatches / 2, 2),
            "host_syncs_per_iter": round(syncs / 2, 2),
        })
    return out


def predict_dispatch_census(rows=2048, features=8, iters=20, calls=6,
                            num_leaves=15):
    """Per-predict-call dispatch/host-sync counts for the serve plan,
    fused (quantized pack + Pallas traversal) vs unfused (ISSUE-12 — the
    serving twin of the training censuses above).  The whole point of the
    one-program plan is that EITHER traversal costs exactly one compiled
    dispatch and one device_get per raw predict call: the fused kernel
    rides inside the same jitted program, so fusion can never add
    launches.  The output-transform path (raw_score=False) adds one eager
    dispatch + one sync — the documented convert-output cost
    (docs/SERVING.md).  Returns one blob per path, pinned by
    tests/test_profile_census.py."""
    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import serve

    rng = np.random.RandomState(0)
    X = rng.randn(rows, features)
    X[rng.rand(rows, features) < 0.05] = np.nan
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": num_leaves,
                     "verbosity": -1}, lgb.Dataset(X, label=y), iters)
    out = []
    for name, kw in (("unfused", {"quantize": "off",
                                  "traverse": "unfused"}),
                     ("fused", {"quantize": "int16",
                                "traverse": "fused"})):
        blob = {"path": name}
        for raw in (True, False):
            pred = serve.Predictor(bst, raw_score=raw, **kw)
            plan = pred.plan
            pred.predict(X[:64])             # compile outside the census
            counts = {"dispatch": 0, "sync": 0}
            wrapped = []

            def wrap(obj, attr):
                fn = getattr(obj, attr)

                def counting(*a, __fn=fn, **k):
                    counts["dispatch"] += 1
                    return __fn(*a, **k)

                setattr(obj, attr, counting)
                wrapped.append((obj, attr, fn))

            # the plan's ONE dispatch seam: every compiled predict launch
            # (jit or AOT executable alike) goes through _call
            wrap(plan, "_call")
            orig_get = jax.device_get

            def counting_get(x):
                counts["sync"] += 1
                return orig_get(x)

            jax.device_get = counting_get
            try:
                for _ in range(calls):
                    pred.predict(X[:64])
            finally:
                jax.device_get = orig_get
                for obj, attr, fn in wrapped:
                    setattr(obj, attr, fn)
            key = "raw" if raw else "transform"
            blob[f"dispatches_per_predict_{key}"] = round(
                counts["dispatch"] / calls, 2)
            blob[f"host_syncs_per_predict_{key}"] = round(
                counts["sync"] / calls, 2)
        blob["quantize"] = kw["quantize"]
        blob["traverse_active"] = pred.plan.traverse_mode
        out.append(blob)
    # The census's plans (device-resident packs) must not stay live past
    # it: callers may census the process-wide buffer set afterwards, and
    # a PredictPlan is a reference cycle (jitted closures capture the
    # plan) — clear the cache AND collect so the packs free now.
    import gc
    pred = plan = None
    serve.clear_plan_cache()
    gc.collect()
    return out


def census_from_log(path):
    """Dispatch-wait / host-bookkeeping census replayed from a telemetry
    JSONL log's ``train.iter`` events (``tpu_telemetry_log``), so the one
    training artifact answers the census question without re-running
    training.  Returns the summary blob (``iters`` == 0 when the log holds
    no iteration events)."""
    from tools.telemetry_report import load_events

    events, problems = load_events(path)
    iters = [e for e in events if e["kind"] == "train.iter"]
    if not iters:
        return {"path": path, "iters": 0, "skipped_lines": len(problems)}
    disp = sum(float(e.get("dispatch_wait_s") or 0.0) for e in iters)
    host = sum(float(e.get("host_s") or 0.0) for e in iters)
    n = len(iters)
    return {
        "path": path,
        "iters": n,
        "pack_sizes": sorted({int(e.get("pack_size", 1)) for e in iters}),
        "mean_wall_s": round((disp + host) / n, 6),
        "mean_dispatch_wait_s": round(disp / n, 6),
        "mean_host_s": round(host / n, 6),
        "dispatch_share": round(disp / (disp + host), 4)
        if disp + host > 0 else None,
        # count from train.checkpoint events, the single source both the
        # per-round AND the pack path emit (pack-path snapshots land at
        # pack boundaries, after the rounds' train.iter events)
        "checkpoint_writes": sum(
            1 for e in events if e["kind"] == "train.checkpoint"),
        "skipped_lines": len(problems),
    }


def _count_host_syncs(run, warmup):
    """Run ``warmup()`` then ``run()`` with jax.device_get instrumented;
    returns the number of device_get calls ``run`` performed.  Every
    per-iteration host sync in the training loop goes through
    jax.device_get (the deferred degenerate-stop fetch, linear/renew leaf
    pulls, CEGB feature pulls), so this census captures exactly the
    round-trips the pack path exists to eliminate."""
    import jax

    warmup()
    counter = {"n": 0}
    orig = jax.device_get

    def counting(x):
        counter["n"] += 1
        return orig(x)

    jax.device_get = counting
    try:
        run()
    finally:
        jax.device_get = orig
    return counter["n"]


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--from-log":
        # Census replay from a telemetry JSONL log — no training, no jax.
        import json as _json
        for path in sys.argv[2:] or [()]:
            if not path:
                print("usage: profile_iter.py --from-log LOG.jsonl ...")
                return
            print(_json.dumps(census_from_log(path)))
        return
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 3

    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from bench import FEATURES, bench_params, make_higgs_like

    X, y = make_higgs_like(rows, FEATURES)
    # bench.py's own config builder, so the trace profiles the SAME
    # compiled program the bench measured
    params = bench_params()
    ds = lgb.Dataset(X, label=y)
    ds.construct(params)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update()                                    # compile
    np.array(jax.device_get(bst._gbdt.scores[:8]))  # fence

    trace_dir = "/tmp/tpu_trace"
    t0 = time.time()
    with jax.profiler.trace(trace_dir):
        for _ in range(iters):
            t_it = time.time()
            bst.update()
            np.array(jax.device_get(bst._gbdt.scores[:8]))
            print(f"iter wall: {time.time() - t_it:.3f}s")
    total = time.time() - t0
    print(f"{iters} iters in {total:.3f}s "
          f"({rows * iters / total / 1e6:.2f} M row-iters/s)")
    print(f"trace: {trace_dir} (tensorboard --logdir {trace_dir})")

    # ---- host-sync census: per-round loop vs iteration-packed loop ------
    n = max(iters, 2)
    legacy = lgb.Booster(params=params, train_set=ds)
    syncs_legacy = _count_host_syncs(
        run=lambda: [legacy.update() for _ in range(n)],
        warmup=legacy.update)
    packed = lgb.Booster(params=params, train_set=ds)
    if not packed._gbdt.iter_pack_plan(n)[1]:
        # update_pack would silently fall back to the per-round loop here;
        # reporting that under a "packed" label would be a lie.
        print(f"host syncs/iter: per-round={syncs_legacy / n:.2f} "
              f"({syncs_legacy} device_get in {n} iters); pack path "
              f"unavailable for this config "
              f"({packed._gbdt.iter_pack_degrade_reason()})")
        return
    syncs_packed = _count_host_syncs(
        run=lambda: packed.update_pack(n),
        warmup=lambda: packed.update_pack(n))
    print(f"host syncs/iter: per-round={syncs_legacy / n:.2f} "
          f"({syncs_legacy} device_get in {n} iters), "
          f"packed={syncs_packed / n:.2f} "
          f"({syncs_packed} device_get in one {n}-round pack)")

    # ---- non-fused fallback paths (GOSS / CEGB / linear_tree) -----------
    print("non-fused dispatch census (used_fused=False paths):")
    for blob in nonfused_dispatch_census(rows=min(rows, 65536)):
        print(f"  {blob['path']:<12} used_fused={blob['used_fused']!s:<5} "
              f"dispatches/iter={blob['dispatches_per_iter']:<6} "
              f"host_syncs/iter={blob['host_syncs_per_iter']}")

    # ---- fused wave kernel (tpu_wave_kernel, ISSUE-7) -------------------
    print("fused-wave census (histogram dispatches per wave):")
    for blob in fused_wave_census(rows=min(rows, 16384)):
        print(f"  {blob['wave_kernel']:<8} active={blob['fused_active']!s:<5} "
              f"hist_dispatches/wave={blob['hist_dispatches_per_wave']} "
              f"(leaf_batch={blob['leaf_batch']}) "
              f"program_dispatches/iter={blob['dispatches_per_iter']}")

    # ---- serve predict path (tpu_traverse_kernel, ISSUE-12) -------------
    print("predict dispatch census (serve plan, fused vs unfused):")
    for blob in predict_dispatch_census(rows=min(rows, 8192)):
        print(f"  {blob['path']:<8} traverse={blob['traverse_active']:<8} "
              f"dispatches/predict={blob['dispatches_per_predict_raw']} "
              f"host_syncs/predict={blob['host_syncs_per_predict_raw']} "
              f"(+transform: {blob['dispatches_per_predict_transform']}/"
              f"{blob['host_syncs_per_predict_transform']})")


if __name__ == "__main__":
    main()
