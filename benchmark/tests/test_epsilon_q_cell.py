"""What PR 37 adds to the yardstick: the configuration ``epsilon-q``
(``epsilon``'s rows under LightGBM 4's quantized training), its cell under
the traffic kind ``train_quantized``, the quantized comparison
(``compare_quantized.py``) and one reader of a scope path segment
(``quantize_s_per_iter``) — against hand-computed values on the tiny scoped
trace and on hand-built levels, and a CPU rehearsal of the cell from
``BENCHMARK.json`` itself at 300 columns in which the control and every
planted fault come out not ``correct``.  Run with the other benchmark
tests: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.

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

from benchmark import compare, compare_quantized, layer_metrics, run, trace  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "tests", "tiny_scoped_trace.json")) as _f:
    FIXTURE = json.load(_f)
CELL = "epsilon-q.train"
METRIC = "quantize_s_per_iter"
FACTS = {"iters": 2, "window": (1000.0, 11000.0),
         "needed": {"rows_hist": 3548.0, "hist_ops": 3548.0 * 7 * 2},
         "peak": {"ops_per_s": 3.93e14, "bytes_per_s": 8.19e11}}


def _quantized(obj):
    """The fixture as a quantized program writes it: its one non-kernel
    ``grow/setup`` operation (the pad, [10800, 11500)) moved under the
    ``quantize`` segment."""
    obj = copy.deepcopy(obj)
    for e in obj["planes"][0]["lines"][1]["events"]:
        if e[3] and e[3].endswith("grow/setup/pad:"):
            e[3] = e[3].replace("grow/setup/", "grow/setup/quantize/")
    return trace.from_json(obj)


def test_quantize_reader_on_the_tiny_scoped_trace():
    read = layer_metrics.reader(METRIC)
    # [10800, 11500) clipped at the window's end: 200 ns over 2 iterations
    assert read(_quantized(FIXTURE), FACTS) == pytest.approx(200e-9 / 2,
                                                             rel=1e-9)
    # the enclosing phase keeps the time
    plain = trace.from_json(copy.deepcopy(FIXTURE))
    grow_state = layer_metrics.reader("grow_state_s_per_iter")
    assert grow_state(_quantized(FIXTURE), FACTS) == grow_state(plain, FACTS)


def test_nothing_to_read_is_none():
    read = layer_metrics.reader(METRIC)
    # an unquantized program, or the parent's: no quantize segment
    assert read(trace.from_json(copy.deepcopy(FIXTURE)), FACTS) is None
    assert read(_quantized(FIXTURE), dict(FACTS, peak=None)) is None


def test_the_configuration_is_epsilons_under_quantized_training():
    entry = {c["name"]: c for c in MANIFEST["configs"]}["epsilon-q"]
    assert entry["file"] == "benchmark/configs/epsilon_q.json"
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "epsilon.json")) as f:
        base = json.load(f)
    assert cfg["source"] == entry["source"] and cfg["reduced"] == []
    assert "use_quantized_grad" in cfg["source"]
    assert "Epsilon 400K x 2000" in cfg["source"]
    for key in ("generator", "data", "rows", "features", "published"):
        assert cfg[key] == base[key], key
    added = {"use_quantized_grad": True, "num_grad_quant_bins": 4,
             "stochastic_rounding": True, "quant_train_renew_leaf": False}
    assert cfg["params"] == dict(base["params"], **added)
    assert cfg["precision"] == "int8"
    assert cfg["work"] == dict(base["work"], grad_bytes=1)
    limits = cfg["correct"]["limits"]
    assert set(limits) == set(compare_quantized.NUMBERS[:6]) | {
        "quant_scale_gap", "quant_level_gap", "quant_bias_z",
        "quant_draw_gap"}
    assert limits["quant_level_gap"] == 0 and limits["leaves_short"] == 0
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("epsilon-q", "train_quantized", 1)
    traffic = run.load_cell(CELL)["traffic"]
    assert traffic["kind"] == "train_quantized"
    assert traffic["warmup_iters"] >= traffic["checked_trees"] == 2
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m["workloads"]}
    eps = {m["name"] for m in MANIFEST["per_layer"]
           if "epsilon.train" in m["workloads"]}
    assert listed == eps | {METRIC}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert CELL in e2e["train_s_per_iter"]["workloads"]


def _hand_levels(rng, n=20000, bins=4, **kw):
    g = rng.randn(n) * 0.3
    h = rng.rand(n) * 0.25
    return g, h, compare_quantized.make_levels(g, h, bins, rng, **kw)


def test_level_readings_on_hand_built_levels():
    rng = np.random.RandomState(5)
    g, h, sound = _hand_levels(rng)

    def read(levels, bins=4):
        return compare_quantized.level_readings(levels, g, h, bins, 1e-4, 8)

    got, draws = read(sound)
    assert got["quant_scale_gap"] < 1e-6 and got["quant_level_gap"] == 0
    assert got["quant_bias_z"] < 5
    # fresh draws against the same gradients: independent
    _, again = read(compare_quantized.make_levels(g, h, 4, rng))
    assert compare_quantized.draw_z(draws, again) < 5
    # the same uniforms twice: the draws are the same
    u = rng.rand(2, len(g))
    _, d1 = read(compare_quantized.make_levels(g, h, 4, rng, uniforms=u))
    _, d2 = read(compare_quantized.make_levels(g, h, 4, rng, uniforms=u))
    assert compare_quantized.draw_z(d1, d2) > 50
    # two levels where the configuration says four: the scale is double
    assert read(compare_quantized.make_levels(g, h, 2, rng))[0][
        "quant_scale_gap"] == pytest.approx(1.0, rel=1e-6)
    # round to nearest: biased in every bucket
    assert read(compare_quantized.make_levels(
        g, h, 4, rng, stochastic=False))[0]["quant_bias_z"] > 20
    # one row two levels away: counted, exactly
    gq, hq, sg, sh = sound
    off = gq.copy()
    off[7] = np.clip(int(off[7]) + 2, -127, 127)
    assert read((off, hq, sg, sh))[0]["quant_level_gap"] == 1 / len(g)


# 300 columns, as test_epsilon_cell.py rehearses epsilon.train
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
    import lightgbm_tpu as lgb
    booster, comparison = lgb.Booster, compare.compare
    res = _rehearse(control=1)
    assert (lgb.Booster, compare.compare) == (booster, comparison)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    assert set(res["metrics"]) == {"train_s_per_iter", "setup_s"}
    assert res["device"]["platform"] == "cpu"      # never a device number
    limits = run.load_cell(CELL)["config"]["correct"]["limits"]
    assert set(res["compared"]) == set(limits)
    assert len(res["stand_ins"]) == 8
    for name, readings in res["stand_ins"].items():
        assert [k for k, v in readings.items()
                if k in limits and v > limits[k]], (name, readings)
    from lightgbm_tpu.telemetry import registry
    gauges = registry().snapshot()["gauges"]
    assert (gauges["hist.dtype"], gauges["quant.bins"],
            gauges["quant.stochastic"]) == (8, 4, 1)


def test_rehearsal_traced_reports_what_a_cpu_can():
    res = _rehearse(trace_flag=1)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"dispatches_per_iter"}
    assert res["metrics"]["dispatches_per_iter"]["value"] == 1.0


def test_a_program_without_the_door_is_refused_at_once(monkeypatch):
    from lightgbm_tpu.models.gbdt import GBDT
    monkeypatch.delattr(GBDT, "last_levels")
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"], require_tpu=False, shrink=SHRINK,
                  out=out)
    assert rc == 3 and out.getvalue() == ""
