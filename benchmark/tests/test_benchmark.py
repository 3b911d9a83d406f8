"""The benchmark's own tests: the manifest's shape, the yardstick's
arithmetic on hand-made inputs, the plain reference against the program at a
small size, and a CPU rehearsal of a whole run — sound, and with the timed
path broken underneath.  Run with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.

Nothing here describes a TPU topology or touches jax at import time.
"""

import io
import json
import os
import re
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import copy  # noqa: E402

from benchmark import compare, generators, layer_metrics  # noqa: E402
from benchmark import reference as ref  # noqa: E402
from benchmark import run, trace, work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
ALL_METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]

# A configuration that waits for the program to be mended (its ``status``
# says why) is in no cell of ``BENCHMARK.json``.  The tests rehearse it from
# a manifest of their own, with ``lambdarank_norm=false``: the one setting
# under which the program and LightGBM's definition are the same computation
# today, so the rehearsal proves the reference's LambdaRank and not the fault.
WAITING = copy.deepcopy(MANIFEST)
WAITING["configs"].append({"name": "msltr", "source": "see the file",
                           "file": "benchmark/configs/msltr.json",
                           "reduced": [], "why": "waiting"})
WAITING["workloads"].append({"name": "msltr.train", "config": "msltr",
                             "traffic": "train", "chips": 1,
                             "why": "waiting"})
for _m in WAITING["end_to_end"]:
    if "workloads" in _m:         # ``setup_s`` has none: every cell reports it
        _m["workloads"] = _m["workloads"] + ["msltr.train"]
REHEARSED = CELLS + ["msltr.train"]


# ------------------------------------------------------------ the manifest

def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]
    assert all(0 < m["bound"] <= 0.1 for m in MANIFEST["end_to_end"])


def test_every_name_is_an_identifier_and_every_unit_short():
    names = ([c["name"] for c in MANIFEST["configs"]] + CELLS
             + [w["config"] for w in MANIFEST["workloads"]]
             + [w["traffic"] for w in MANIFEST["workloads"]]
             + [m["name"] for m in ALL_METRICS]
             + [m["layer"] for m in MANIFEST["per_layer"]]
             + [k for c in MANIFEST["configs"] for k in c["reduced"]])
    assert [n for n in names if not NAME.match(n)] == []
    assert [m["unit"] for m in ALL_METRICS if not UNIT.match(m["unit"])] == []
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in ALL_METRICS}) == len(ALL_METRICS)
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert all(len(c["source"]) <= 200 for c in MANIFEST["configs"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_exists(cell):
    ctx = run.load_cell(cell)
    cfg_entry = {c["name"]: c for c in MANIFEST["configs"]}[
        ctx["cell"]["config"]]
    assert cfg_entry["file"].startswith(tuple(MANIFEST["paths"]))
    cfg = ctx["config"]
    assert os.path.exists(os.path.join(BENCH, "generators",
                                       cfg["generator"] + ".py"))
    assert os.path.exists(os.path.join(BENCH, "objectives",
                                       cfg["params"]["objective"] + ".py"))
    assert sorted(cfg["reduced"]) == sorted(cfg_entry["reduced"])
    assert all(k in cfg for k in cfg["reduced"])
    assert set(cfg["correct"]["limits"]) <= set(compare.NUMBERS)
    assert os.path.exists(os.path.join(
        BENCH, "kinds", ctx["traffic"]["kind"] + ".py"))


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_metric_has_reader_moves_and_workloads(metric):
    m = {x["name"]: x for x in MANIFEST["per_layer"]}[metric]
    assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                       metric + ".py"))
    assert callable(layer_metrics.reader(metric))
    assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
    moved = {x["name"]: x for x in MANIFEST["end_to_end"]}[m["moves"]]
    assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))
    assert "roofline" not in metric or metric.endswith("_roofline")


def test_no_cell_config_or_metric_name_in_run_py():
    with open(os.path.join(BENCH, "run.py")) as f:
        src = f.read()
    names = (CELLS + [c["name"] for c in MANIFEST["configs"]]
             + [m["name"] for m in ALL_METRICS])
    assert [n for n in names if re.search(r"\b%s\b" % re.escape(n), src)] \
        == []


# ------------------------------------------- trace reduction, by hand

@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(BENCH, "tests", "tiny_trace.json")) as f:
        return trace.from_json(json.load(f))


FACTS = {"iters": 2, "window": (1000.0, 11000.0),
         "needed": {"hist_ops": 10.0, "hist_bytes": 819.0,
                    "ops": 20.0, "bytes": 1638.0},
         "peak": {"ops_per_s": 1.97e14, "bytes_per_s": 8.19e11}}

# device ops in the window: [1000,1200] + [1500,5000] + [6000,10000] =
# 7700 ns busy of 10000; kernels (tpu_custom_call) 1500 + 2000 = 3500 ns
HAND = {"dispatches_per_iter": 1.0,
        "device_idle_share": 23.0,
        "kernel_s_per_iter": 1.75e-6,
        "xla_s_per_iter": 2.1e-6,
        "hist_roofline": 100 * 1e-9 / 1.75e-6,
        "train_step_mfu": 100 * 2e-9 / 5e-6}


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_on_the_tiny_recorded_trace(tiny, metric):
    assert tiny.window() == FACTS["window"]
    assert layer_metrics.reader(metric)(tiny, FACTS) == \
        pytest.approx(HAND[metric], rel=1e-9)


def test_trace_breakdown_and_empty_trace(tiny):
    assert len(tiny.device_planes()) == 1          # not the SparseCore plane
    assert tiny.busy_ns(FACTS["window"]) == 7700.0
    # the enclosing while (4 us) is control flow, not an operation
    assert tiny.top_ops(FACTS["window"], 3) == [
        ["fusion.1 fusion", 2e-6], ["fused_wave_call.5 custom-call", 2e-6],
        ["histogram_flat.2 custom-call", 1.5e-6]]
    assert dict(map(tuple, tiny.idle_gaps(FACTS["window"]))) == {
        "train/fused_iter": pytest.approx(3e-7),
        "bench/iter": pytest.approx(1e-6),
        "bench/fence": pytest.approx(1e-6)}
    empty = trace.Trace([{"name": "/host:CPU", "lines": []}])
    for metric in HAND:
        if metric != "train_step_mfu":      # needs no device event
            assert layer_metrics.reader(metric)(empty, FACTS) is None


# ------------------------------------------------- needed work and peaks

def _leaf(i, c):
    return {"leaf_index": i, "leaf_value": 0.1 * (i + 1), "leaf_count": c,
            "leaf_weight": float(c)}


THREE_LEAVES = {"num_leaves": 3, "tree_structure": {
    "split_index": 0, "split_feature": 0, "threshold": 0.5, "split_gain": 9.0,
    "decision_type": "<=", "internal_value": 0.0, "internal_count": 100,
    "left_child": _leaf(0, 30),
    "right_child": {
        "split_index": 1, "split_feature": 1, "threshold": -1.0,
        "split_gain": 4.0, "decision_type": "<=", "internal_value": 0.0,
        "internal_count": 70, "left_child": _leaf(1, 60),
        "right_child": _leaf(2, 10)}}}


def test_rows_hist_on_a_hand_built_three_leaf_tree():
    # root 100 + min(30, 70) + min(60, 10)
    assert work.rows_hist(THREE_LEAVES) == 140
    w = {"bin_bytes": 1, "grad_bytes": 4, "row_state_bytes": 24,
         "grad_ops_per_row": 12}
    n = work.needed(THREE_LEAVES, 100, 5, w)
    assert n["hist_bytes"] == 140 * (5 + 8) and n["hist_ops"] == 140 * 10
    assert n["bytes"] == n["hist_bytes"] + 2400
    assert work.least_seconds(1.97e14, 8.19e11 * 2,
                              FACTS["peak"]) == (2.0, "bytes")


def test_peaks_refuse_an_unknown_device_kind():
    assert work.peaks("TPU v5 lite", "float32")["bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError, match="no peaks recorded"):
        work.peaks("TPU v9 imaginary", "float32")


def test_reference_walks_and_sums_the_hand_built_tree():
    t = ref.flatten_tree(THREE_LEAVES)
    X = np.array([[0.0, 0.0], [1.0, -2.0], [1.0, 0.0], [0.5, 9.0]],
                 np.float32)
    assert ref.leaf_of_rows(X, t).tolist() == [0, 1, 2, 0]
    sums = ref.node_sums(t, np.array([[30.0], [60.0], [10.0]]))
    assert sums[:, 0].tolist() == [100.0, 70.0]
    assert ref.node_leaves(t, 1).tolist() == [1, 2]
    assert ref.round_bf16(np.float32([1.0, 1.00390625, 3.14159])).tolist() \
        == [1.0, 1.0, 3.140625]


def test_generators_repeat_and_take_large_seeds():
    kw = dict(rows=1000, features=7, group=120, data_seed=5)
    a = generators.make("msltr_like", 2 ** 31 + 12345, **kw)
    b = generators.make("msltr_like", 2 ** 31 + 12345, **kw)
    c = generators.make("msltr_like", 2 ** 31 + 12346, **kw)
    assert np.array_equal(a["X"], b["X"]) and a["X"].dtype == np.float32
    assert a["group"].tolist() == [120] * 8 + [40]
    # another seed: the same rows and labels, the columns in another order
    assert not np.array_equal(a["X"], c["X"])
    assert np.array_equal(a["label"], c["label"])
    assert sorted(map(tuple, a["X"].T)) == sorted(map(tuple, c["X"].T))
    h = generators.make("higgs_like", 3, rows=500, features=4, data_seed=5)
    k = generators.make("higgs_like", 3, rows=500, features=4, data_seed=6)
    assert not np.array_equal(h["label"], k["label"])


# ---------------------------------------- a whole run, rehearsed on the CPU

SHRINK = {"data": {"rows": 12000},
          "params": {"num_leaves": 15, "min_sum_hessian_in_leaf": 2.0,
                     "lambdarank_norm": False},
          "correct": {"score_sample_rows": 3000}}


def _rehearse(cell, trace_flag=0, control=0, seed=2 ** 31 + 77):
    out = io.StringIO()
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace_flag), "--control",
                   str(control)], require_tpu=False, shrink=SHRINK, out=out,
                  manifest=WAITING)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", REHEARSED)
def test_rehearsal_last_line_is_the_contracts_object(cell):
    res = _rehearse(cell, control=1)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    want = {m["name"] for m in run.metrics_of(WAITING, "end_to_end", cell)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"      # never a device number
    for c in res["compared"].values():
        assert c["value"] <= c["limit"]
    # the control (bfloat16 sums) and every fault read some number they
    # report over the same limit
    limits = run.load_cell(cell, manifest=WAITING)["config"]["correct"][
        "limits"]
    assert len(res["stand_ins"]) == 6
    for name, readings in res["stand_ins"].items():
        over = [k for k, v in readings.items()
                if k in limits and v > limits[k]]
        if "best_split_gap" in limits or not name.endswith(
                ("runner_up_feature", "scan_short")):
            assert over, (name, readings)


def test_rehearsal_traced_reports_per_layer_metrics_only():
    res = _rehearse(CELLS[0], trace_flag=1)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"dispatches_per_iter"}  # no device: CPU
    assert res["metrics"]["dispatches_per_iter"]["value"] == 1.0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_run_refuses_without_a_tpu():
    out = io.StringIO()
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"], require_tpu=True, shrink=SHRINK, out=out)
    assert rc != 0 and out.getvalue() == ""


def _break_state_unchanged(monkeypatch):
    import lightgbm_tpu as lgb
    real = lgb.Booster.update

    def update(self, *a, **kw):
        before = self._gbdt.scores
        done = real(self, *a, **kw)
        self._gbdt.scores = before          # the step returns its state
        return done
    monkeypatch.setattr(lgb.Booster, "update", update)


def _break_half_batch(monkeypatch):
    from benchmark.kinds import train
    real = train._make_dataset

    def make(lgb, data, params):
        X = data["X"].copy()
        half = len(X) // 2
        X[half:2 * half] = X[:half]         # half of the rows never seen
        return real(lgb, {**data, "X": X}, params)
    monkeypatch.setattr(train, "_make_dataset", make)


def _break_answer_altered(monkeypatch):
    import lightgbm_tpu as lgb
    real = lgb.Booster.dump_model

    def dump(self, *a, **kw):
        model = real(self, *a, **kw)
        node = model["tree_info"][1]["tree_structure"]
        while "left_child" in node and "split_index" in node["left_child"]:
            node = node["left_child"]
        node["left_child"]["leaf_value"] *= -1.0
        return model
    monkeypatch.setattr(lgb.Booster, "dump_model", dump)


def _break_tree_stopped_early(monkeypatch):
    from benchmark.kinds import train
    real = train._make_dataset

    def make(lgb, data, params):
        params["num_leaves"] = 7            # the Booster gets these too
        return real(lgb, data, params)
    monkeypatch.setattr(train, "_make_dataset", make)


@pytest.mark.parametrize("cell", REHEARSED)
@pytest.mark.parametrize("fault", [_break_state_unchanged, _break_half_batch,
                                   _break_answer_altered,
                                   _break_tree_stopped_early])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch)
    res = _rehearse(cell)
    assert res["correct"] is False
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in res["compared"].values())
