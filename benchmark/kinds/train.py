"""Traffic kind ``train``: steady boosting on one Booster.

Set-up (counted from the process's start): jax and its devices, data from
the seed, ``Dataset.construct`` (binning), the Booster, the compile or
cache load, the warm-up iteration.  Then the window: one
``Booster.update()`` per iteration, fenced by a read-back of a score slice
after every one (so the clock, not the enqueue, ends the window), until
``--seconds`` have passed; the window closes at the fence after the
iteration in flight.  ``train_s_per_iter`` is the whole window over the
iterations completed in it.

With ``--trace 1`` a few iterations inside the window run under jax's
profiler; the per-layer readers reduce that trace.  After the window the
peak device memory is read, the scores and trees are fetched, the program's
state is dropped, and only then does the plain reference run.
"""

from __future__ import annotations

import gc
import os
import shutil
import sys
import time

import numpy as np


def _log(msg: str) -> None:
    sys.stderr.write(f"[bench {time.strftime('%H:%M:%S')}] {msg}\n")
    sys.stderr.flush()


def _device_block(jax, devices) -> dict:
    """The device as jax reports it.  ``memory_peak_bytes`` is the fullest
    chip's peak from ``memory_stats()``: ``peak_bytes_in_use`` (the buffers
    the process held: bins, scores, trees) plus ``peak_bytes_reserved`` (the
    arena the runtime reserves for the loaded programs' temporaries, which
    ``peak_bytes_in_use`` leaves out on this runtime and which is most of
    what a boosting iteration holds — PERF.md, Findings PR 24)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        _log(f"memory_stats {d}: {stats}")
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def _make_dataset(lgb, data: dict, params: dict):
    ds = lgb.Dataset(data["X"], label=data["label"], group=data.get("group"))
    ds.construct(params)
    return ds


def run(ctx: dict):
    from .. import compare, generators, layer_metrics, trace as tracemod, work
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
    from lightgbm_tpu.utils.jax_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    peak = (work.peaks(devices[0].device_kind, config["precision"])
            if devices[0].platform == "tpu" else None)

    # ---- set-up
    data = generators.make(config["generator"], ctx["seed"], **config["data"])
    rows, features = data["X"].shape
    t_data = time.time()
    params = dict(config["params"])
    ds = _make_dataset(lgb, data, params)
    t_bin = time.time()
    bst = lgb.Booster(params=params, train_set=ds)

    def fence():
        np.asarray(jax.device_get(bst._gbdt.scores[:8]))

    finished = False
    for _ in range(int(traffic["warmup_iters"])):
        finished = bst.update() or finished
    fence()
    t_w0 = time.time()
    setup_s = t_w0 - ctx["t0"]
    _log(f"set-up {setup_s:.1f} s (data {t_data - ctx['t0']:.1f}, binning "
         f"{t_bin - t_data:.1f}, booster+compile+warm-up {t_w0 - t_bin:.1f}); "
         f"compile cache {cache_dir}")

    # ---- the window
    ann = jax.profiler.TraceAnnotation
    skip, n_traced = int(traffic["trace_skip_iters"]), int(traffic["trace_iters"])
    trace_dir = os.path.join(ctx["root"], ".bench_out", "trace", cell["name"])
    traced_window = None
    tracing_done = not ctx["trace"]
    iters = 0
    ends = [t_w0]              # each iteration's end, for a look at stalls
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

    # ---- what the window produced; then drop the program's state
    device = _device_block(jax, devices)
    final_scores = np.asarray(jax.device_get(bst._gbdt.scores))
    trees = bst.dump_model()["tree_info"]
    del bst, ds, fence
    gc.collect()
    _log(f"window {t_w1 - t_w0:.2f} s, {iters} iterations, "
         f"{len(trees)} trees; peak {device['memory_peak_bytes']} bytes")
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
        first = int(traffic["warmup_iters"]) + skip
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
    readings = compare.compare(
        config, data, trees, final_scores, ctx["seed"],
        int(traffic["checked_trees"]), stand_ins=ctx["control"])
    ok, compared = compare.verdict(readings["program"],
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
