"""What PR 31 adds to the yardstick: the configuration ``epsilon`` at its
published size (``configs/epsilon.json``), its generator, its cell, and one
reader (``hist_chunk_s_per_iter``) — against a hand count on the tiny scoped
trace with ``chunks<K>`` written in, and a CPU rehearsal of the cell from
``BENCHMARK.json`` itself at a width that still chunks and tiles.  Run with
the other benchmark tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``.

Nothing here describes a TPU topology or touches jax at import time.
"""

import copy
import io
import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, generators, layer_metrics, run, trace  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "tests", "tiny_scoped_trace.json")) as _f:
    FIXTURE = json.load(_f)
CELL = "epsilon.train"
METRIC = "hist_chunk_s_per_iter"
FACTS = {"iters": 2, "window": (1000.0, 11000.0),
         "needed": {"rows_hist": 3548.0, "hist_ops": 3548.0 * 7 * 2},
         "peak": {"ops_per_s": 1.97e14, "bytes_per_s": 8.19e11}}


def _chunked(obj):
    """The fixture as a program writes a histogram of several launches:
    ``chunks2/cols4`` before the ``rows<R>`` of the root's two
    ``histogram_flat`` launches, and three operations around them under the
    same path — a slice inside the window, a transpose that straddles its
    end, and the ``while`` that encloses nothing of it."""
    obj = copy.deepcopy(obj)
    events = obj["planes"][0]["lines"][1]["events"]
    for e in events:
        if e[3] and "/rows6000/" in e[3]:
            e[3] = e[3].replace("/rows6000/", "/chunks2/cols4/rows6000/")
    path = ("jit(fused)/jit(main)/grow/setup/chunks2/cols4/rows6000/"
            "jit(histogram_flat)/")
    events += [
        ["%fusion.20 = u8[64,4]{1,0} fusion(u8[64,7]{1,0} %p.20), kind=kLoop",
         7000.0, 300.0, path + "slice:"],
        ["%fusion.21 = u8[64,4]{1,0} fusion(u8[64,7]{1,0} %p.21), kind=kLoop",
         7200.0, 400.0, path + "slice:"],          # overlaps the first
        ["%transpose.22 = f32[7,128,3]{2,1,0} transpose(f32[3,7,128]{2,1,0} "
         "%p.22), dimensions={1,2,0}", 10900.0, 500.0, path + "transpose:"],
        ["%while.23 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %t.23), "
         "condition=%c.23, body=%b.23", 2000.0, 100.0, path + "while:"]]
    return trace.from_json(obj)


def test_chunk_reader_against_a_hand_count():
    read = layer_metrics.reader(METRIC)
    # the slices cover [7000, 7600) = 600 ns; the transpose [10900, 11400)
    # is clipped at the window's end to 100 ns; the kernels under the same
    # path and the enclosing while do not count: 700 ns over 2 iterations
    assert read(_chunked(FIXTURE), FACTS) == pytest.approx(700e-9 / 2,
                                                           rel=1e-9)
    # rows<R> and cols<C> stay where the other readers look for them
    assert layer_metrics.reader("hist_cells_useful")(
        _chunked(FIXTURE), FACTS) == pytest.approx(
            100 * 3548 * 7 / ((8192 * 0 + 6000 * 4) / 2), rel=1e-9)


def test_nothing_to_read_is_none():
    read = layer_metrics.reader(METRIC)
    # a one-launch histogram, or the parent's program: no chunks<K>
    assert read(trace.from_json(copy.deepcopy(FIXTURE)), FACTS) is None
    assert read(_chunked(FIXTURE), dict(FACTS, peak=None)) is None
    assert read(trace.Trace([{"name": "/host:CPU", "lines": []}]),
                FACTS) is None


def test_the_configuration_is_upstreams_and_nothing_is_cut():
    entry = {c["name"]: c for c in MANIFEST["configs"]}["epsilon"]
    assert entry["file"] == "benchmark/configs/epsilon.json"
    assert entry["reduced"] == []
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert "Dataset Preparation" in cfg["source"]
    assert "How We Benchmark" in cfg["source"]
    assert cfg["reduced"] == []
    assert cfg["rows"] == cfg["published"]["rows"] == 400000
    assert cfg["features"] == cfg["published"]["features"] == 2000
    assert cfg["data"] == {"rows": 400000, "features": 2000,
                           "data_seed": 24}
    p = cfg["params"]
    assert (p["objective"], p["num_leaves"], p["max_bin"],
            p["learning_rate"], p["min_data_in_leaf"],
            p["min_sum_hessian_in_leaf"]) == ("binary", 255, 255, 0.1, 1,
                                              100.0)
    assert "histogram_pool_size" not in p
    assert set(p) - {"metric", "verbosity"} - set(cfg["assumed"]) == {
        "objective", "num_leaves", "learning_rate", "min_data_in_leaf",
        "min_sum_hessian_in_leaf"}                 # the source's own keys
    assert set(cfg["correct"]["limits"]) == set(compare.NUMBERS[:6])
    assert cfg["correct"]["sampled_nodes_per_tree"] >= 6
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("epsilon", "train", 1)
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m["workloads"]}
    assert len(listed) == 16 and METRIC in listed
    assert not {"hist_rows_useful", "rank_grad_s_per_iter"} & listed


def test_generator_rows_are_unit_length_fixed_and_permuted_by_the_seed():
    big = 2 ** 31 + 77
    a = generators.make("epsilon_like", big, rows=640, features=40,
                        data_seed=5)
    b = generators.make("epsilon_like", big, rows=640, features=40,
                        data_seed=5)
    c = generators.make("epsilon_like", 3, rows=640, features=40,
                        data_seed=5)
    assert a["X"].dtype == np.float32 and a["X"].shape == (640, 40)
    assert np.array_equal(a["X"], b["X"])
    np.testing.assert_allclose(np.linalg.norm(a["X"], axis=1), 1.0,
                               rtol=1e-6)
    assert set(np.unique(a["label"])) == {0.0, 1.0}
    # another seed: the same rows and labels, the columns in another order,
    # the order generators.columns draws
    assert np.array_equal(a["label"], c["label"])
    order_a = generators.rng_of(big).permutation(40)
    order_c = generators.rng_of(3).permutation(40)
    assert np.array_equal(a["X"][:, np.argsort(order_a)][:, order_c], c["X"])
    d = generators.make("epsilon_like", 3, rows=640, features=40,
                        data_seed=6)
    assert not np.array_equal(c["label"], d["label"])


# 300 columns: past the scan's tiling threshold (256) and, where the Pallas
# kernel runs, past one launch's columns at 255 bins (2 chunks of 150)
SHRINK = {"data": {"rows": 6000, "features": 300},
          "params": {"num_leaves": 15, "min_sum_hessian_in_leaf": 2.0},
          "correct": {"score_sample_rows": 3000}}


def _rehearse(trace_flag=0, control=0):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 77),
                   "--seconds", "1", "--trace", str(trace_flag),
                   "--control", str(control)],
                  require_tpu=False, shrink=SHRINK, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_rehearsal_sound_and_the_stand_ins_are_refused():
    """The cell from ``BENCHMARK.json`` itself at a small size on the CPU:
    every compared number under the configuration's limit, and the control
    and every planted fault over at least one."""
    res = _rehearse(control=1)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    assert set(res["metrics"]) == {"train_s_per_iter", "setup_s"}
    assert res["device"]["platform"] == "cpu"      # never a device number
    limits = run.load_cell(CELL)["config"]["correct"]["limits"]
    assert set(res["compared"]) == set(limits)
    assert len(res["stand_ins"]) == 6
    for name, readings in res["stand_ins"].items():
        assert [k for k, v in readings.items()
                if k in limits and v > limits[k]], (name, readings)
    from lightgbm_tpu.telemetry import registry
    gauges = registry().snapshot()["gauges"]
    assert gauges["scan.tile"] == 128               # 300 columns: tiled
    assert gauges["grow.leaf_hist_bytes"] == 15 * 300 * 255 * 3 * 4


def test_rehearsal_traced_reports_what_a_cpu_can():
    res = _rehearse(trace_flag=1)
    assert res["correct"] is True
    # no device plane on a CPU: only the host-span metric, never a 0
    assert set(res["metrics"]) == {"dispatches_per_iter"}
    assert {"busy_s", "window_s"} <= set(res["device"])
