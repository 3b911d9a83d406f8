"""The host's side of a run, from the program's own records (ISSUE 35,
docs/OBSERVABILITY.md "Iteration records"):

- one record per ``Booster.update()`` on the fused, the unfused and the
  packed path, every field typed, ``period_ns`` = enter to enter; RF too;
- the ring is bounded; ``tpu_telemetry=off`` leaves no record and the same
  trees;
- under ``jax.profiler`` every ``train/iter`` span carries ``iter`` and
  ``t_ns`` and the record lies on the span through that anchor;
- the stall rule: one planted sleep, one warning naming the iteration;
  nine planted, eight lines;
- a fresh ``jax.jit`` between two updates shows in ``jit.compiles`` and in
  the record that interval belongs to;
- ``train.iter`` events are views of the records;
- the benchmark's five readers on synthetic rings.
"""

import glob
import json
import os
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import iters
from lightgbm_tpu.utils.log import Log

pytestmark = pytest.mark.telemetry

INT_FIELDS = ("iter", "count", "enter_ns", "dispatched_ns", "period_ns",
              "cpu_ns", "thread_cpu_ns", "voluntary_switches",
              "involuntary_switches", "major_faults", "minor_faults",
              "gc_collections", "compiles")
PROC_FIELDS = ("runq_wait_ns", "steal_ticks")      # where /proc has them


def _data(n=1500, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


def _booster(extra=None, n=1500):
    X, y = _data(n)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "metric": "none"}
    params.update(extra or {})
    return lgb.Booster(params, lgb.Dataset(X, label=y))


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.set_enabled(True)
    telemetry.reset_spans()          # the iteration ring goes with them
    lines = []
    Log.reset_callback(lines.append)
    yield lines
    Log.reset_callback(None)
    telemetry.close_log()
    telemetry.set_enabled(True)


def _logistic(scores, _ds, y=_data()[1]):
    p = 1.0 / (1.0 + np.exp(-scores.reshape(-1)))
    return p - y, p * (1.0 - p)


def _run_path(path):
    """(booster, program names, count) after 4 iterations (2 packs)."""
    if path == "fused":
        bst = _booster()
        for _ in range(4):
            bst.update()
        return bst, ["fused_iter"], 1
    if path == "unfused":
        bst = _booster({"objective": "custom"})
        for _ in range(4):
            bst.update(fobj=_logistic)
        return bst, ["grow_apply"], 1
    if path == "rf":
        bst = _booster({"boosting": "rf", "bagging_fraction": 0.7,
                        "bagging_freq": 1})
        for _ in range(4):
            bst.update()
        return bst, ["grow_apply"], 1
    bst = _booster()
    for _ in range(2):
        assert bst.update_pack(3) == (3, False)
    return bst, ["pack_k3"], 3


@pytest.mark.parametrize("path", ["fused", "unfused", "packed", "rf"])
def test_one_record_per_update_every_field_typed(path):
    _bst, programs, count = _run_path(path)
    recs = telemetry.iter_records()
    assert len(recs) == (2 if path == "packed" else 4)
    assert [r["iter"] for r in recs] == list(range(1, 1 + len(recs) * count,
                                                   count))
    for r, nxt in zip(recs, recs[1:]):          # the closed ones
        assert r["programs"] == programs and r["count"] == count
        assert r["period_ns"] == nxt["enter_ns"] - r["enter_ns"]
        assert r["enter_ns"] <= r["dispatched_ns"] <= nxt["enter_ns"]
        for k in INT_FIELDS:
            assert isinstance(r[k], int) and r[k] >= 0, (k, r[k])
        for k in PROC_FIELDS:
            assert r[k] is None or (isinstance(r[k], int) and r[k] >= 0)
        assert isinstance(r["compile_s"], float)
        assert set(r) == set(iters._BLANK)
    last = recs[-1]                              # open until the next one
    assert last["period_ns"] is None and last["cpu_ns"] is None
    assert last["dispatched_ns"] >= last["enter_ns"]
    assert telemetry.last_iter_record()["iter"] == last["iter"]


def test_a_source_that_fails_reads_none_from_then_on(monkeypatch):
    def boom():
        raise OSError("no such file")
    srcs = [list(s) for s in iters._SOURCES]
    srcs[2][0] = boom                            # /proc/self/schedstat
    monkeypatch.setattr(iters, "_SOURCES", srcs)
    bst = _booster()
    for _ in range(3):
        bst.update()
    recs = telemetry.iter_records()
    assert recs[0]["runq_wait_ns"] is None and recs[1]["runq_wait_ns"] is None
    assert isinstance(recs[1]["cpu_ns"], int)
    assert srcs[2][0] is not boom                # dropped once, not retried


def test_ring_is_bounded():
    for i in range(iters.RING + 50):
        with telemetry.iter_record(i + 1) as rec:
            telemetry.note_program(rec, "fused_iter")
    recs = telemetry.iter_records()
    assert len(recs) == iters.RING == 4096
    assert recs[0]["iter"] == 51 and recs[-1]["iter"] == iters.RING + 50
    telemetry.reset_spans()
    assert telemetry.iter_records() == []


def test_off_leaves_no_record_and_the_same_trees():
    def trees(mode):
        telemetry.reset_spans()
        bst = _booster({"tpu_telemetry": mode})
        for _ in range(4):
            bst.update()
        n = len(telemetry.iter_records())
        body = [ln for ln in bst.model_to_string().splitlines()
                if "tpu_telemetry" not in ln]
        return n, body
    n_on, on = trees("on")
    n_off, off = trees("off")
    assert (n_on, n_off) == (4, 0)
    assert on == off                             # bitwise: the text is exact


def test_train_iter_spans_carry_iter_and_the_anchor(tmp_path):
    import jax
    from benchmark import host_timeline
    bst = _booster()
    bst.update()                                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        bst.update()
        np.asarray(jax.device_get(bst._gbdt.scores[:8]))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = host_timeline.iter_spans(path)
    assert sorted(s["iter"] for s in spans) == [2, 3, 4]
    off = host_timeline.anchor_ns(spans)
    recs = {r["iter"]: r for r in telemetry.iter_records()}
    for s in spans:
        r = recs[s["iter"]]
        assert s["t_ns"] == r["enter_ns"]        # ONE clock read on entry
        start = s["start_ns"] + off
        assert abs(r["enter_ns"] - start) < 5e5  # 0.5 ms
        assert r["dispatched_ns"] <= start + s["duration_ns"] + 5e5
    # the dispatch spans kept their paths and carry the same identifier
    assert "train/fused_iter" in telemetry.span_totals()
    assert "train/iter" not in telemetry.span_totals()
    data = jax.profiler.ProfileData.from_file(path)
    inner = [dict(e.stats).get("iter") for pl in data.planes
             for ln in pl.lines for e in ln.events
             if e.name == "train/fused_iter"]
    assert sorted(inner) == [2, 3, 4]


def _loop(bst, n, sleep_after):
    for i in range(1, n + 1):
        bst.update()
        if i in sleep_after:
            time.sleep(0.5)


def test_one_planted_sleep_gives_one_warning_naming_the_iteration(_fresh):
    bst = _booster()
    _loop(bst, 20, {12})
    stalls = [ln for ln in _fresh if "from one update() to the next" in ln]
    assert len(stalls) == 1
    assert stalls[0].startswith("[LightGBM-TPU] [Warning] iteration 12 took")
    rec = json.loads(stalls[0][stalls[0].index("{"):])
    assert rec["iter"] == 12 and rec["period_ns"] >= 5e8
    assert rec["programs"] == ["fused_iter"]
    # process idle and blocked: the sleep burnt no CPU
    assert rec["cpu_ns"] < 2.5e8


def test_nine_planted_sleeps_give_eight_lines(_fresh):
    bst = _booster()
    _loop(bst, 48, set(range(8, 44, 4)))         # nine, 3 sound ones between
    stalls = [ln for ln in _fresh if "from one update() to the next" in ln]
    assert len(stalls) == iters.STALL_LINES == 8
    assert [int(ln.split()[3]) for ln in stalls] == list(range(8, 40, 4))


def test_a_fresh_jit_shows_in_the_counters_and_in_its_record():
    import jax
    import jax.numpy as jnp

    def sums():
        h = telemetry.registry().snapshot()["histograms"]
        return {k: (v["count"], v["sum"]) for k, v in h.items()}
    h0 = sums()
    bst = _booster()
    for _ in range(3):
        bst.update()
    before = telemetry.registry().counter("jit.compiles").value
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(5)).block_until_ready()
    bst.update()                                  # closes record 3
    after = telemetry.registry().counter("jit.compiles").value
    assert after > before
    recs = telemetry.iter_records()
    assert recs[2]["compiles"] >= 1 and recs[2]["compile_s"] > 0.0
    assert recs[1]["compiles"] == 0
    counters = telemetry.registry().snapshot()["counters"]
    assert counters['jit.compiles{fun_name="<lambda>"}'] >= 1
    h1 = sums()

    def grew(name):
        c0, s0 = h0.get(name, (0, 0.0))
        return h1[name][0] - c0, h1[name][1] - s0
    jax_side = 0.0
    for name in ("jit.trace_seconds", "jit.lower_seconds",
                 "jit.backend_seconds"):
        assert grew(name)[1] > 0.0
        n, secs = grew(name + '{fun_name="fused"}')
        assert n == 1 and secs > 0.0              # nested traces: not twice
        jax_side += secs
    # the call-side account of the same program, laid beside it
    n, call = grew('compile.seconds{label="train/fused_iter"}')
    assert n == 1 and 0.0 < jax_side <= call


@pytest.mark.parametrize("pack", [1, 3])
def test_train_iter_events_are_views_of_the_records(tmp_path, pack):
    log = str(tmp_path / "run.jsonl")
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "metric": "none", "tpu_telemetry_log": log,
              "tpu_iter_pack": pack}
    lgb.train(params, lgb.Dataset(X, label=y), 6)
    events = [json.loads(ln) for ln in open(log)]
    its = [e for e in events if e["kind"] == "train.iter"]
    assert [e["iteration"] for e in its] == [1, 2, 3, 4, 5, 6]
    assert events[-1]["kind"] == "train.end"
    recs = telemetry.iter_records()
    assert len(recs) == 6 // pack
    for e in its:
        assert {"wall_s", "dispatch_wait_s", "host_s", "pack_size",
                "checkpoint_s", "health", "period_s", "cpu_s",
                "involuntary_switches", "major_faults",
                "compiles"} <= set(e)
        r = recs[(e["iteration"] - 1) // pack]
        assert e["pack_size"] == pack
        assert e["dispatch_wait_s"] == round(
            (r["dispatched_ns"] - r["enter_ns"]) / 1e9 / pack, 6)
        assert e["wall_s"] >= e["dispatch_wait_s"] >= 0.0
        assert e["host_s"] >= 0.0
        if r["period_ns"] is None:               # the run's last record
            assert e["period_s"] is None and e["cpu_s"] is None
        else:
            assert e["period_s"] == e["wall_s"] == round(
                r["period_ns"] / 1e9 / pack, 6)
            assert e["cpu_s"] == round(r["cpu_ns"] / 1e9 / pack, 6)
            assert e["compiles"] == r["compiles"]
    assert sum(e["period_s"] is None for e in its) == pack


def test_report_tool_shows_the_record_and_marks_stalls():
    from tools import telemetry_report as tool
    assert (tool.STALL_RATIO, tool.STALL_MIN_S, tool.STALL_HISTORY,
            tool.STALL_MIN_HISTORY) == (
        iters.STALL_RATIO, iters.STALL_MIN_S, iters.STALL_HISTORY,
        iters.STALL_MIN_HISTORY)
    events = [{"kind": "train.iter", "iteration": i, "wall_s": 0.4,
               "dispatch_wait_s": 0.001, "host_s": 0.399, "pack_size": 1,
               "period_s": 2.2 if i == 9 else 0.4, "cpu_s": 0.01,
               "involuntary_switches": 3, "compiles": 0}
              for i in range(1, 13)]
    rows = tool.iteration_rows(events)
    assert [r[0] for r in rows if r[-1] == "STALL"] == [9]
    assert rows[8][7:11] == ("2.2000", "0.0100", 3, 0)


# ------------------------------------------------- the benchmark's readers

def _ring(periods, programs=("fused_iter",), first_iter=1, t0=10 ** 18,
          dispatch_ns=400_000, compiles=0):
    """Closed records with the given periods (s) and one open at the end."""
    out, t = [], t0
    for j, p in enumerate(list(periods) + [None]):
        rec = dict(iters._BLANK, iter=first_iter + j, count=1, enter_ns=t,
                   dispatched_ns=t + dispatch_ns, programs=list(programs))
        if p is not None:
            rec.update(period_ns=int(p * 1e9), cpu_ns=10 ** 7,
                       thread_cpu_ns=10 ** 6, compiles=compiles,
                       compile_s=0.0, involuntary_switches=0,
                       major_faults=0)
            t += int(p * 1e9)
        out.append(rec)
    return out


FACTS = {"window": None, "iters": 3, "peak": {"ops_per_s": 1.0}}


def _read(name, ring, monkeypatch, facts=FACTS):
    from benchmark import layer_metrics
    monkeypatch.setattr(telemetry, "iter_records", lambda: ring)
    return layer_metrics.reader(name)(object(), facts)


def test_readers_on_a_sound_window(monkeypatch, capsys):
    ring = _ring([0.400 + 0.001 * j for j in range(40)])
    assert _read("host_dispatch_s_per_iter", ring, monkeypatch) == \
        pytest.approx(4e-4)
    ratio = _read("slowest_iter_ratio", ring, monkeypatch)
    assert 1.0 <= ratio < 1.05                   # detrended: trees lengthen
    assert "slowest iteration of 39" in capsys.readouterr().err


def test_readers_on_one_stalled_period(monkeypatch, capsys):
    periods = [0.400] * 40
    periods[25] = 2.274
    ring = _ring(periods)
    assert _read("slowest_iter_ratio", ring, monkeypatch) == \
        pytest.approx(2.274 / 0.400)
    err = capsys.readouterr().err
    assert '"iter": 26' in err and '"cpu_ns"' in err


def test_readers_leave_out_a_prefix_of_another_program(monkeypatch):
    # msltr-goss: 10 unsampled set-up iterations, then the sampled program,
    # whose first iteration compiled and whose second is the window's first
    ring = (_ring([1.0] * 10, dispatch_ns=9_000_000)[:-1]
            + _ring([80.0], ("fused_iter[sampled]",), 11, 10 ** 18 + 10 ** 10,
                    compiles=1)[:-1]
            + _ring([0.318] * 30, ("fused_iter[sampled]",), 12,
                    10 ** 18 + 10 ** 11, dispatch_ns=3_000_000))
    from benchmark import host_timeline
    win = host_timeline.window_records(object(), FACTS, ring)
    assert [r["iter"] for r in win] == list(range(13, 42))
    assert _read("host_dispatch_s_per_iter", ring, monkeypatch) == \
        pytest.approx(3e-3)
    assert _read("slowest_iter_ratio", ring, monkeypatch) == \
        pytest.approx(1.0)


def test_readers_leave_out_the_profilers_records(monkeypatch):
    from benchmark import host_timeline
    periods = [0.4] * 30
    periods[1], periods[4] = 5.0, 9.0            # start_trace, stop_trace
    ring = _ring(periods)
    # the trace's clock starts 7 s before the records' t0 + ...: any offset
    off = ring[0]["enter_ns"] - 7_000_000_000
    spans = [{"iter": r["iter"], "t_ns": r["enter_ns"],
              "start_ns": float(r["enter_ns"] - off), "duration_ns": 5e5}
             for r in ring[2:5]]
    monkeypatch.setattr(host_timeline, "iter_spans", lambda path=None: spans)
    lo = ring[1]["enter_ns"] + int(4.9e9) - off  # bench/traced opens ...
    hi = ring[4]["enter_ns"] + int(0.41e9) - off     # ... and closes
    facts = dict(FACTS, window=(float(lo), float(hi)))

    class Tr:
        pass
    win = host_timeline.window_records(Tr(), facts, ring)
    assert [r["iter"] for r in win] == [3, 4] + list(range(6, 31))
    assert host_timeline.alignment(Tr(), facts, ring) == (3, 3, 0.0)
    monkeypatch.setattr(telemetry, "iter_records", lambda: ring)
    from benchmark import layer_metrics
    assert layer_metrics.reader("slowest_iter_ratio")(Tr(), facts) == \
        pytest.approx(1.0)


def test_readers_read_nothing_from_too_few_records_or_none(monkeypatch):
    for name in ("host_dispatch_s_per_iter", "slowest_iter_ratio"):
        assert _read(name, _ring([0.4] * 10), monkeypatch) is None   # 9 left
        assert _read(name, _ring([0.4] * 11), monkeypatch) is not None
        assert _read(name, [], monkeypatch) is None
        assert _read(name, _ring([0.4] * 40), monkeypatch,
                     dict(FACTS, peak=None)) is None     # no device: CPU
    # a program from before the records (the parent): no such function
    monkeypatch.delattr(telemetry, "iter_records")
    from benchmark import layer_metrics
    for name in ("host_dispatch_s_per_iter", "slowest_iter_ratio"):
        assert layer_metrics.reader(name)(object(), FACTS) is None


def test_setup_readers_read_the_spans_and_jaxs_account(monkeypatch):
    from benchmark import layer_metrics
    for name in ("setup_data_init_s", "setup_booster_init_s"):
        assert layer_metrics.reader(name)(object(), FACTS) is None  # reset
    bst = _booster()
    bst.update()
    totals = telemetry.span_totals()
    assert totals["data/init"]["count"] == 1
    assert totals["train/booster_init"]["count"] == 1
    assert layer_metrics.reader("setup_data_init_s")(object(), FACTS) == \
        totals["data/init"]["seconds"]
    assert layer_metrics.reader("setup_booster_init_s")(object(), FACTS) == \
        totals["train/booster_init"]["seconds"]
    hists = telemetry.registry().snapshot()["histograms"]
    want = sum(hists[n]["sum"] for n in (
        "jit.trace_seconds", "jit.lower_seconds", "jit.backend_seconds",
        "jit.cache_load_seconds") if n in hists)
    assert want > 0.0
    assert layer_metrics.reader("setup_jit_s")(object(), FACTS) == want
    # a program without the listener (the parent): an empty registry
    monkeypatch.setattr(telemetry, "registry", telemetry.MetricsRegistry)
    assert layer_metrics.reader("setup_jit_s")(object(), FACTS) is None
