"""What PR 33 adds to the yardstick: the configuration ``msltr-goss`` (the
LightGBM paper's LETOR + GOSS run), its cell under the traffic kind
``train_sampled``, the sampled comparison (``compare_sampled.py``) and two
readers of scope path segments — against hand-computed values on the tiny
scoped trace, and a CPU rehearsal of the cell from ``BENCHMARK.json`` itself
in which the control and every planted fault come out not ``correct``.  Run
with the other benchmark tests: ``JAX_PLATFORMS=cpu python -m pytest
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

from benchmark import compare, compare_sampled, layer_metrics, run, trace  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "tests", "tiny_scoped_trace.json")) as _f:
    FIXTURE = json.load(_f)
CELL = "msltr-goss.train"
FACTS = {"iters": 2, "window": (1000.0, 11000.0),
         "needed": {"rows_hist": 3548.0, "hist_ops": 3548.0 * 7 * 2},
         "peak": {"ops_per_s": 1.97e14, "bytes_per_s": 8.19e11}}
# the fixture's boost/gradients operation, [0, 1200) clipped to 200 ns,
# moved under .../boost/gradients/sample/; its grow/partition scatter,
# [1500, 2500), under .../grow/partition/oob_route/
SEGMENT_OF = {"sample_s_per_iter": ("boost/gradients/", "sample"),
              "oob_route_s_per_iter": ("grow/partition/", "oob_route")}
HAND = {"sample_s_per_iter": 200e-9 / 2, "oob_route_s_per_iter": 1000e-9 / 2}


def _with_segment(metric):
    under, seg = SEGMENT_OF[metric]
    obj = copy.deepcopy(FIXTURE)
    for e in obj["planes"][0]["lines"][1]["events"]:
        if e[3] and under in e[3]:
            e[3] = e[3].replace(under, f"{under}{seg}/")
    return trace.from_json(obj)


@pytest.mark.parametrize("metric", sorted(HAND))
def test_segment_reader_on_the_tiny_scoped_trace(metric):
    read = layer_metrics.reader(metric)
    assert read(_with_segment(metric), FACTS) == \
        pytest.approx(HAND[metric], rel=1e-9)
    # the enclosing phase keeps the time: the phase readers read the same
    phase_metric = {"sample_s_per_iter": "boost_s_per_iter",
                    "oob_route_s_per_iter": "partition_s_per_iter"}[metric]
    plain = trace.from_json(copy.deepcopy(FIXTURE))
    assert layer_metrics.reader(phase_metric)(_with_segment(metric), FACTS) \
        == layer_metrics.reader(phase_metric)(plain, FACTS)


@pytest.mark.parametrize("metric", sorted(HAND))
def test_nothing_to_read_is_none(metric):
    read = layer_metrics.reader(metric)
    # a program from before PR 33 (the parent): no such segment — None, no 0
    assert read(trace.from_json(copy.deepcopy(FIXTURE)), FACTS) is None
    assert read(_with_segment(metric), dict(FACTS, peak=None)) is None
    assert read(trace.Trace([{"name": "/host:CPU", "lines": []}]),
                FACTS) is None


def test_the_configuration_is_msltrs_with_the_papers_goss():
    entry = {c["name"]: c for c in MANIFEST["configs"]}["msltr-goss"]
    assert entry["file"] == "benchmark/configs/msltr_goss.json"
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "msltr_upstream.json")) as f:
        base = json.load(f)
    assert cfg["source"] == entry["source"] and cfg["reduced"] == []
    assert cfg["data"] == base["data"] and cfg["work"] == base["work"]
    added = {"data_sample_strategy": "goss", "top_rate": 0.1,
             "other_rate": 0.1, "bagging_seed": 3}
    assert cfg["params"] == dict(base["params"], **added)
    n = cfg["data"]["rows"]
    assert compare_sampled.goss_sizes(n, cfg["params"]) == \
        (10, 227000, 227000, np.float32(9.0))
    limits = cfg["correct"]["limits"]
    assert set(limits) == set(compare.NUMBERS[:6]) | {
        "sample_size_gap", "sample_top_gap", "sample_draw_gap"}
    assert limits["sample_size_gap"] == 0 and limits["leaves_short"] == 0
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("msltr-goss", "train_sampled", 1)
    traffic = run.load_cell(CELL)["traffic"]
    assert traffic["kind"] == "train_sampled" and traffic["warmup_iters"] == 11
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m["workloads"]}
    msltr = {m["name"] for m in MANIFEST["per_layer"]
             if "msltr.train" in m["workloads"]}
    assert listed == msltr | set(HAND)
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert CELL in e2e["train_s_per_iter"]["workloads"]


def _goss_sample(rng, s, top_k, other_k, amplify):
    order = np.argsort(-s, kind="stable")
    drawn = rng.choice(order[top_k:], other_k, replace=False)
    rows = np.concatenate([order[:top_k], drawn])
    w = np.concatenate([np.ones(top_k, np.float32),
                        np.full(other_k, amplify, np.float32)])
    o = np.argsort(rows)
    return rows[o], w[o]


def test_sample_readings_on_hand_built_samples():
    rng = np.random.RandomState(5)
    n, params = 20000, {"top_rate": 0.1, "other_rate": 0.1,
                        "learning_rate": 0.1}
    sizes = compare_sampled.goss_sizes(n, params)
    _, top_k, other_k, amplify = sizes
    assert (top_k, other_k, float(amplify)) == (2000, 2000, 9.0)
    s = rng.rand(n) ** 3

    def read(sample, sampled=True, earlier=None):
        return compare_sampled.sample_readings(sample, n, sampled, sizes, s,
                                               1e-3, earlier)

    sound = _goss_sample(rng, s, top_k, other_k, amplify)
    got = read(sound)
    assert got["sample_size_gap"] == 0 and got["sample_top_gap"] == 0
    assert got["sample_draw_gap"] < 4
    # an unsampled tree holds every row; a sample there is a fault
    assert read(None, sampled=False) == {"sample_size_gap": 0.0}
    assert read(sound, sampled=False)["sample_size_gap"] > 0.5
    assert read(None)["sample_size_gap"] > 0          # every row, too late
    # one drawn row short; one row twice; a wrong amplification
    rows, w = sound
    assert read((rows[w == 1], w[w == 1]))["sample_size_gap"] == 0.5
    twice = (np.append(rows, rows[0]), np.append(w, w[0]))
    assert read(twice)["sample_size_gap"] > 0
    assert read((rows, np.where(w == 1, w, 8.0)))["sample_size_gap"] == 1.0
    # the top set taken at random: nine in ten lie below the threshold
    shuffled = (rng.permutation(n)[:top_k + other_k], w)
    assert read(shuffled)["sample_top_gap"] > 0.8
    # the drawn set taken as the next-largest rows; the same draw twice
    order = np.argsort(-s, kind="stable")
    nxt = np.sort(order[:top_k + other_k])
    wn = np.where(np.isin(nxt, order[:top_k]), 1.0, 9.0).astype(np.float32)
    assert read((nxt, wn))["sample_draw_gap"] > 50
    drawn = rows[w != 1]
    assert read(sound, earlier=drawn)["sample_draw_gap"] > 50
    fresh = _goss_sample(rng, s, top_k, other_k, amplify)
    assert read(fresh, earlier=drawn)["sample_draw_gap"] < 5


def test_a_tree_that_ran_out_of_splits_is_not_one_stopped_early():
    read = compare_sampled.short_tree
    full = read(255, np.full(255, 500.0), 255, 100.0)
    assert full == {"leaves_short": 0.0, "leaves_lacking": 0.0,
                    "heaviest_unsplit": 0.0}
    # 240 leaves, none with room for two children of 100: excused
    spent = read(240, np.full(240, 150.0), 255, 100.0)
    assert spent["leaves_short"] == 0 and spent["leaves_lacking"] == 15
    assert spent["heaviest_unsplit"] == 1.5
    # within a tenth of the bare two children: still excused
    assert read(240, np.r_[np.full(239, 90.0), 215.0], 255,
                100.0)["leaves_short"] == 0
    # a leaf of 5 x min_sum_hessian left whole: stopped early
    early = read(240, np.r_[np.full(239, 90.0), 500.0], 255, 100.0)
    assert early["leaves_short"] == 15 and early["heaviest_unsplit"] == 5.0


SHRINK = {"data": {"rows": 12000},
          "params": {"num_leaves": 15, "min_sum_hessian_in_leaf": 2.0},
          "correct": {"score_sample_rows": 3000}}


def test_rehearsal_sound_and_every_planted_fault_not_correct():
    """The cell from ``BENCHMARK.json`` itself at a small size on the CPU:
    ten unsampled iterations and the first sampled one in set-up, sampled
    boosting in the window; every compared number under the configuration's
    limit, and the control and each planted fault over at least one."""
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 33),
                   "--seconds", "1", "--trace", "0", "--control", "1"],
                  require_tpu=False, shrink=SHRINK, out=out)
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["attempted"] >= 1, res["compared"]
    assert set(res["metrics"]) == {"train_s_per_iter", "setup_s"}
    limits = run.load_cell(CELL)["config"]["correct"]["limits"]
    assert set(res["compared"]) == set(limits)
    assert set(res["stand_ins"]) == {
        "control", "fault_no_amplify", "fault_drawn_left_out",
        "fault_next_largest", "fault_same_draw", "fault_oob_not_updated",
        "fault_sampled_from_0", "fault_runner_up_feature",
        "fault_scan_short"}
    for name, readings in res["stand_ins"].items():
        assert [k for k, v in readings.items()
                if k in limits and v > limits[k]], (name, readings)
