"""Traffic kind ``train_sampled``: steady boosting on one Booster whose
trees are grown on a row sample (GOSS).

As ``kinds/train.py`` (set-up counted from the process's start, one
``Booster.update()`` per iteration fenced by a read-back, the traced
iterations inside the window, the reference only after the program's state
is dropped), with two differences.  The warm-up is long enough to run BOTH
programs a GOSS configuration has — LightGBM leaves the first
``int(1 / learning_rate)`` iterations unsampled (``goss.hpp``), so
``warmup_iters`` covers those and the first sampled one — and the window is
steady sampled boosting.  And the comparison is ``compare_sampled.py``,
which must be told which rows each checked tree was grown on: tree 0 (every
row), the first sampled tree and the window's last.  Their samples are read
from the Booster (``bst._gbdt.last_sample()``, as ``scores`` is read for
the fence) right after the update that grew them — in the warm-up for the
first two, after the window has closed for the last — never inside the
window.

A program that cannot say which rows it grew a tree on (no
``last_sample``) cannot run this kind: it says so and exits at once, before
any data is made.

``run()`` repeats ``kinds/train.py``'s (nothing that is there may be edited
by the PR that added this file); PERF.md section 7 names the fold for a
``benchmark`` issue: one ``run()`` with a hook for the checked trees' row
weights.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from .train import _device_block, _log, _make_dataset


def _sample_of(bst):
    """The sample of the iteration ``bst`` ran last, for
    ``compare_sampled``: ``None`` (every row) or ``(rows, weights)``."""
    sample = bst._gbdt.last_sample()
    return None if sample is None else tuple(np.asarray(a) for a in sample)


def run(ctx: dict):
    from .. import (compare_sampled, generators, layer_metrics,
                    trace as tracemod, work)
    from ..run import metrics_of

    config, traffic, cell = ctx["config"], ctx["traffic"], ctx["cell"]
    import jax
    devices = jax.devices()
    if ctx["require_tpu"] and (devices[0].platform != "tpu"
                               or len(devices) < cell["chips"]):
        _log(f"needs {cell['chips']} TPU chip(s); jax has "
             f"{len(devices)} x {devices[0].platform}: not measuring")
        return None
    devices = devices[:cell["chips"]]
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.gbdt import GBDT
    if not hasattr(GBDT, "last_sample"):
        _log("this program cannot say which rows a tree was grown on "
             "(GBDT.last_sample is missing): the sampled comparison has "
             "nothing to recompute the checked trees from; not measuring")
        return None
    from lightgbm_tpu.utils.jax_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    peak = (work.peaks(devices[0].device_kind, config["precision"])
            if devices[0].platform == "tpu" else None)

    # ---- set-up
    data = generators.make(config["generator"], ctx["seed"], **config["data"])
    rows, features = data["X"].shape
    t_data = time.time()
    params = dict(config["params"])
    first_sampled = compare_sampled.goss_sizes(rows, params)[0]
    warmup = int(traffic["warmup_iters"])
    if warmup <= first_sampled:
        raise SystemExit(f"warmup_iters={warmup} does not reach the first "
                         f"sampled iteration ({first_sampled})")
    ds = _make_dataset(lgb, data, params)
    t_bin = time.time()
    bst = lgb.Booster(params=params, train_set=ds)

    def fence():
        np.asarray(jax.device_get(bst._gbdt.scores[:8]))

    samples = {}
    finished = False
    for i in range(warmup):
        finished = bst.update() or finished
        if i in (0, first_sampled):
            samples[i] = _sample_of(bst)
    fence()
    t_w0 = time.time()
    setup_s = t_w0 - ctx["t0"]
    _log(f"set-up {setup_s:.1f} s (data {t_data - ctx['t0']:.1f}, binning "
         f"{t_bin - t_data:.1f}, booster+compile+warm-up {t_w0 - t_bin:.1f}); "
         f"compile cache {cache_dir}; plan {bst._gbdt.plan}")

    # ---- the window
    ann = jax.profiler.TraceAnnotation
    skip, n_traced = int(traffic["trace_skip_iters"]), int(traffic["trace_iters"])
    trace_dir = os.path.join(ctx["root"], ".bench_out", "trace", cell["name"])
    traced_window = None
    tracing_done = not ctx["trace"]
    iters = 0
    ends = [t_w0]
    while not finished:
        if ctx["trace"] and iters == skip:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced_window = ann(tracemod.names()["window_span"])
            traced_window.__enter__()
        with ann("bench/iter"):
            finished = bst.update()
            with ann("bench/fence"):
                fence()
        iters += 1
        ends.append(time.time())
        if ctx["trace"] and iters == skip + n_traced:
            traced_window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing_done = True
        if ends[-1] - t_w0 >= ctx["seconds"] and tracing_done:
            break
    t_w1 = ends[-1]
    if not tracing_done:                 # training ran out of splits
        traced_window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        n_traced = max(iters - skip, 0)

    # ---- what the window produced (the last tree's sample among it, read
    # now that the window is closed); then drop the program's state
    device = _device_block(jax, devices)
    final_scores = np.asarray(jax.device_get(bst._gbdt.scores))
    trees = bst.dump_model()["tree_info"]
    samples[len(trees) - 1] = _sample_of(bst)
    from lightgbm_tpu.telemetry import registry
    snap = registry().snapshot()
    gauges = {k: v for kind in ("gauges", "counters")
              for k, v in snap[kind].items() if k.startswith("sample.")}
    del bst, ds, fence
    gc.collect()
    _log(f"window {t_w1 - t_w0:.2f} s, {iters} iterations, "
         f"{len(trees)} trees; peak {device['memory_peak_bytes']} bytes; "
         f"sample gauges {gauges}")
    _log("seconds per iteration: "
         + " ".join(f"{b - a:.3f}" for a, b in zip(ends, ends[1:])))

    manifest = ctx["manifest"]
    metrics = {}
    result = {"correct": False, "attempted": iters, "failed": 0,
              "metrics": metrics, "device": device}
    if not ctx["trace"]:
        values = {"train_s_per_iter": (t_w1 - t_w0) / max(iters, 1),
                  "setup_s": setup_s}
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        tr = tracemod.load_xplane(tracemod.find_xplane(trace_dir))
        window = tr.window()
        first = warmup + skip
        needs = [work.needed(t, rows, features, config["work"])
                 for t in trees[first:first + n_traced]]
        facts = {"window": window, "iters": n_traced, "peak": peak,
                 "needed": {k: float(np.mean([n[k] for n in needs]))
                            for k in needs[0]}}
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            v = (layer_metrics.reader(m["name"])(tr, facts)
                 if peak is not None or m["source"] != "device_trace"
                 else None)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = tr.busy_ns(window)
        device["busy_s"] = (busy or 0.0) / 1e9
        device["window_s"] = (window[1] - window[0]) / 1e9
        result["breakdown"] = {"device_ops": tr.top_ops(window),
                               "idle_gaps": tr.idle_gaps(window)}
        result["needed_per_iter"] = facts["needed"]
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- correct: the plain reference, after the program's state is gone
    t_c0 = time.time()
    readings = compare_sampled.compare(
        config, data, trees, final_scores, ctx["seed"], sorted(samples),
        samples, stand_ins=ctx["control"])
    ok, compared = compare_sampled.verdict(readings["program"],
                                           config["correct"]["limits"])
    _log(f"reference and comparison {time.time() - t_c0:.1f} s")
    _log(f"program readings: {readings['program']}")
    result["correct"] = bool(ok and iters > 0)
    if ctx["control"]:
        result["stand_ins"] = {k: v for k, v in readings.items()
                               if k != "program"}
        for k, v in result["stand_ins"].items():
            _log(f"stand-in {k}: {v}")
    result["compared"] = compared
    return result
