"""The scope readers (``benchmark/scopes.py`` and the eight per-layer
metrics built on it) against hand-computed values.  Run with the other
benchmark tests: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.

Nothing here describes a TPU topology or touches jax at import time.
"""

import copy
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import layer_metrics, scopes, trace  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "tests", "tiny_scoped_trace.json")) as _f:
    FIXTURE = json.load(_f)

FACTS = {"iters": 2, "window": (1000.0, 11000.0),
         "needed": {"rows_hist": 3548.0},
         "peak": {"ops_per_s": 1.97e14, "bytes_per_s": 8.19e11}}

# In the window [1000, 11000): busy = [1000,1200] + the while [1500,8000]
# + [8200,9200] + [9300,9800] + [10800,11000] = 8400 ns; kernels 2000 +
# 1000 = 3000 ns; so 5400 ns are not a kernel (what xla_s_per_iter reads).
# Of those: boost 200 (clipped at the window's start) + 500; partition
# 1000; gather 1000; grower state 400 (grow/update/grow/scan: innermost)
# + 200 (clipped at the window's end); and 2100 with no phase: the
# scopeless copy (300) and what only the while covers (100 + 200 + 1500).
# Rows: the kernels that START in the window were handed 8192 + 6000.
HAND = {"boost_s_per_iter": 700e-9 / 2,
        "partition_s_per_iter": 1000e-9 / 2,
        "gather_s_per_iter": 1000e-9 / 2,
        "grow_state_s_per_iter": 600e-9 / 2,
        "unscoped_share": 100 * 2100 / 5400,
        "hist_rows_useful": 100 * 3548 / ((8192 + 6000) / 2)}
SPLIT = ("gather_s_per_iter", "partition_s_per_iter",
         "grow_state_s_per_iter", "boost_s_per_iter")
NEW = sorted(HAND) + ["setup_binning_s", "setup_compile_s"]


@pytest.fixture()
def tiny():
    return trace.from_json(copy.deepcopy(FIXTURE))


def _without_scopes(obj):
    """The same events as ``trace.py`` keeps them: three elements."""
    obj = copy.deepcopy(obj)
    for pl in obj["planes"]:
        for ln in pl["lines"]:
            ln["events"] = [e[:3] for e in ln["events"]]
    return trace.from_json(obj)


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_on_the_tiny_scoped_trace(tiny, metric):
    assert tiny.window() == FACTS["window"]
    assert layer_metrics.reader(metric)(tiny, FACTS) == \
        pytest.approx(HAND[metric], rel=1e-9)


def test_phases_and_unscoped_add_up_to_xla_s_per_iter(tiny):
    split = scopes.split_ns(tiny, FACTS)
    assert split["unscoped"] == 2100.0 and split["non_kernel"] == 5400.0
    xla = layer_metrics.reader("xla_s_per_iter")(
        _without_scopes(FIXTURE), FACTS)
    assert xla == pytest.approx(5400e-9 / 2, rel=1e-12)
    parts = sum(layer_metrics.reader(m)(tiny, FACTS) for m in SPLIT)
    assert parts + split["unscoped"] / 1e9 / FACTS["iters"] == \
        pytest.approx(xla, rel=1e-12)
    # every phase is in exactly one of the four sums
    listed = [p for m in SPLIT for p in scopes.names()["metrics"][m]]
    assert sorted(listed) == sorted(scopes.names()["phases"])


def test_innermost_phase_and_rows_of_a_scope_path():
    phases = scopes.names()["phases"]
    assert scopes.phase_of(
        "jit(f)/while/body/grow/update/grow/scan/reduce_max:", phases) \
        == "grow/scan"
    assert scopes.phase_of("jit(f)/grow/scan/grow/update/x:", phases) \
        == "grow/update"
    assert scopes.phase_of("jit(f)/while/body/select_n:", phases) is None
    assert scopes.phase_of("jit(f)/agrow/scan_more/x:", phases) is None
    assert scopes.phase_of(None, phases) is None
    assert scopes.rows_of(
        "jit(f)/grow/wave_gather/rows8192/jit(k)/pallas_call:") == 8192
    assert scopes.rows_of("jit(f)/grow/setup/arrows12/x:") is None


@pytest.mark.parametrize("metric", NEW)
def test_nothing_to_read_is_none(tiny, metric):
    read = layer_metrics.reader(metric)
    # no chip: never a device number, never a set-up number from a CPU run
    assert read(tiny, dict(FACTS, peak=None)) is None
    if metric.startswith("setup_"):
        return
    empty = trace.Trace([{"name": "/host:CPU", "lines": []}])
    assert read(empty, FACTS) is None
    # a program without phase scopes (the parent): operations carry a path
    # (jit(fused)/while/body/...), no phase, no rows<R>
    parent = copy.deepcopy(FIXTURE)
    for e in parent["planes"][0]["lines"][1]["events"]:
        e[3] = "jit(fused)/jit(main)/while/body/select_n:"
    assert read(trace.from_json(parent), FACTS) is None


def test_setup_readers_read_the_programs_own_span_and_counter(tiny):
    from lightgbm_tpu import telemetry
    telemetry.set_enabled(True)
    binning = layer_metrics.reader("setup_binning_s")
    compiling = layer_metrics.reader("setup_compile_s")
    b0 = binning(tiny, FACTS) or 0.0
    c0 = compiling(tiny, FACTS) or 0.0
    with telemetry.span("data/construct"):
        pass
    with telemetry.span("outer"), telemetry.span("data/construct"):
        pass                    # a nested span's path ends in the name
    with telemetry.span("data/other"):
        pass
    totals = telemetry.span_totals()
    want = (totals["data/construct"]["seconds"]
            + totals["outer/data/construct"]["seconds"])
    assert binning(tiny, FACTS) == pytest.approx(want) and want > b0
    telemetry.note_compile("test/program", 1.5)
    telemetry.note_compile("test/other", 0.25)
    assert compiling(tiny, FACTS) == pytest.approx(c0 + 1.75)


def test_scope_names_are_the_programs_phases():
    from lightgbm_tpu.telemetry import PHASES
    table = scopes.names()
    assert table["phases"] == list(PHASES)
    assert set(table["waiting"]) <= set(PHASES)
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for metric, listed in table["metrics"].items():
        assert set(listed) <= set(PHASES)
        assert per_layer[metric]["source"] == "device_trace"
    for name in NEW:
        assert per_layer[name]["workloads"] == ["higgs.train"]


XSPACE = """
planes {
  name: "/device:TPU:0"
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "hlo_category" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(f)/boost/gradients/mul:" } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()"
    stats { metadata_id: 8 str_value: "loop fusion" }
    stats { metadata_id: 7 str_value: "jit(f)/while/body/grow/partition/x:" }
  } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion()"
    stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8] copy()" } }
  lines { name: "XLA Modules" timestamp_ns: 5000
          events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 } }
  lines { name: "XLA Ops" timestamp_ns: 5000
          events { metadata_id: 1 offset_ps: 1500 duration_ps: 2000500 }
          events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
          events { metadata_id: 3 offset_ps: 4000000 duration_ps: 250 } }
}
planes { name: "/host:CPU" lines { name: "python" timestamp_ns: 5000
  events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench/traced" } } }
"""


def test_xplane_file_is_read_on_the_profilers_clock_with_its_scopes(tmp_path):
    import jax.profiler
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
            XSPACE))
    planes = scopes.load_xplane(path)
    assert [pl["name"] for pl in planes] == ["/device:TPU:0"]
    (line,) = planes[0]["lines"]                  # the operations' line
    assert [e[3] for e in line["events"]] == [
        "jit(f)/while/body/grow/partition/x:",
        "jit(f)/boost/gradients/mul:", None]
    # name, start and duration are what trace.py reads from the same file
    want = trace.load_xplane(path).device_ops(
        trace.load_xplane(path).device_planes()[0])
    assert [e[:3] for e in line["events"]] == want
    assert line["events"][0][1:3] == [5001.0, 2000.0]   # whole ns
