"""Out-of-core streaming training (ISSUE-13, lightgbm_tpu/stream/,
docs/STREAMING.md).

Bitwise discipline: the streamed grower is the mask-layout body driven
chunk-by-chunk, with chunked histogram accumulation SEEDED
(``histogram_from_vals(init=...)``) so the cross-chunk fold replays the
in-core add order — streamed trees pin BITWISE-identical to in-core
training with MESSY multi-iteration fp32 gradients (no exact-sum crutch)
on the CPU backend's scatter impl, and quantized int32 histograms are
unconditionally exact.  The pins run the full engine round loop on both
sides (masks, key folds, shrink epilogue, degenerate stops included).
"""

import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import engine
from lightgbm_tpu.basic import Booster, Dataset
from lightgbm_tpu.serialization import FrameCorruptError
from lightgbm_tpu.stream import (ChunkPlan, ContinualSession,
                                 ResidencyManager, ShardedDataset,
                                 StreamDataset, StreamTrainer, append_rows,
                                 dataset_to_shards, refit_streamed,
                                 train_streamed)

pytestmark = pytest.mark.stream

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, F = 4096, 12
BASE_PARAMS = {
    "objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
    "verbosity": -1, "min_data_in_leaf": 5, "seed": 7,
}
# tiny budget => 8 shards of 512 rows stream as multiple chunks
TINY_BUDGET_MB = 0.02


def _data(seed=11, n=N, f=F):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    X[rng.rand(n) < 0.03, 4] = np.nan
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] + 0.2 * rng.randn(n) > 0
         ).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def data():
    return _data()


@pytest.fixture(scope="module")
def store(data, tmp_path_factory):
    X, y = data
    path = str(tmp_path_factory.mktemp("stream") / "store")
    # the public surface: Dataset.to_shards (ISSUE-13 tentpole API)
    return Dataset(X, label=y, params=BASE_PARAMS).to_shards(
        path, rows_per_shard=512, params=BASE_PARAMS)


def _trees_only(bst) -> str:
    """Model string minus importances/params (streamed runs record the
    tpu_stream_* knobs; everything above that line must be bitwise)."""
    return bst.model_to_string().split("\nfeature_importances")[0]


def _stream_params(extra=None, budget=TINY_BUDGET_MB):
    p = dict(BASE_PARAMS, tpu_stream_budget_mb=budget)
    p.update(extra or {})
    return p


# ------------------------------------------------------------------- store
def test_store_roundtrip(data, store):
    X, y = data
    td = Dataset(X, label=y, params=BASE_PARAMS).construct(BASE_PARAMS)
    assert store.num_data == N and store.num_features == F
    assert store.num_shards == 8
    whole = np.concatenate([np.asarray(b) for _lo, _hi, b
                            in store.iter_shards()])
    np.testing.assert_array_equal(whole, td.binned.bins)
    np.testing.assert_array_equal(store.label, td.label)
    # mmap and checksum-validated reads agree
    np.testing.assert_array_equal(np.asarray(store.shard_bins(3, mmap=True)),
                                  store.shard_bins(3, mmap=False))
    from lightgbm_tpu.stream import bin_identity
    assert store.bin_identity == bin_identity(td.binned.mappers,
                                              td.binned.max_num_bins)
    assert store.verify() == []


def test_store_corrupt_frame_detected_and_rebuilt(data, tmp_path):
    """Corrupt-frame fallback: damage is DETECTED at read (sha256 frame),
    reported by verify(), and ``to_shards(resume=True)`` rebuilds exactly
    the damaged shard while keeping valid ones."""
    X, y = data
    ds = Dataset(X, label=y, params=BASE_PARAMS)
    st = dataset_to_shards(ds, str(tmp_path / "s"), rows_per_shard=512,
                           params=BASE_PARAMS)
    victim = os.path.join(st.path, st.manifest.shards[2])
    blob = bytearray(open(victim, "rb").read())
    blob[100] ^= 0xFF
    open(victim, "wb").write(bytes(blob))
    with pytest.raises(FrameCorruptError):
        st.shard_bins(2, mmap=False)
    assert st.verify() == [2]
    # truncation is caught even on the mmap fast path (length check)
    with open(victim, "r+b") as fh:
        fh.truncate(64)
    with pytest.raises(FrameCorruptError):
        st.shard_bins(2, mmap=True)
    st2 = dataset_to_shards(ds, str(tmp_path / "s"), rows_per_shard=512,
                            params=BASE_PARAMS, resume=True)
    assert st2.verify() == []
    np.testing.assert_array_equal(np.asarray(st2.shard_bins(2)),
                                  st.shard_bins(2, mmap=False))


def test_store_open_refuses_torn_build(tmp_path):
    with pytest.raises(Exception, match="not a shard store"):
        ShardedDataset.open(str(tmp_path / "nothing"))


def test_store_identity_mismatch_refused(data, store, tmp_path):
    X, y = data
    other = dataset_to_shards(
        Dataset(X, label=y, params=dict(BASE_PARAMS, max_bin=63)),
        str(tmp_path / "o"), rows_per_shard=1024,
        params=dict(BASE_PARAMS, max_bin=63))
    with pytest.raises(Exception, match="identity mismatch"):
        store.assert_compatible(other.bin_identity)


def test_append_rows_rebins_through_frozen_mappers(data, tmp_path):
    X, y = data
    ds = Dataset(X, label=y, params=BASE_PARAMS)
    st = dataset_to_shards(ds, str(tmp_path / "a"), rows_per_shard=512,
                           params=BASE_PARAMS)
    X2, y2 = _data(seed=99, n=700)
    st2 = append_rows(st, X2, y2)
    assert st2.num_data == N + 700
    assert st2.bin_identity == st.bin_identity
    td = Dataset(X, label=y, params=BASE_PARAMS).construct(BASE_PARAMS)
    expect = td.binned.apply(X2)
    got = np.concatenate([np.asarray(b) for _l, _h, b
                          in st2.iter_shards()])[N:]
    np.testing.assert_array_equal(got, expect)
    np.testing.assert_array_equal(st2.label[N:], y2)


# -------------------------------------------------------------- residency
def test_chunk_plan_budget_validation(store):
    with pytest.raises(ValueError, match="budget"):
        ChunkPlan(store, budget_bytes=1024)   # one 512-row shard > half
    plan = ChunkPlan(store, budget_bytes=int(TINY_BUDGET_MB * 2 ** 20))
    assert plan.num_chunks > 1
    assert plan.chunk_rows * F * 1 == plan.chunk_bytes


def test_residency_sweep_budget_and_prefetch(store):
    budget = int(TINY_BUDGET_MB * 2 ** 20)
    with ResidencyManager(store, budget) as rm:
        seen_rows = 0
        for _ci, lo, hi, arr in rm.sweep():
            assert rm.live_bytes() <= budget
            seen_rows += hi - lo
        assert seen_rows == N
        for _ in rm.sweep():
            pass
    s = rm.stats()
    assert s["peak_bytes"] <= budget
    assert s["uploads"] == 2 * rm.plan.num_chunks
    assert s["prefetch_hits"] + s["prefetch_stalls"] == s["uploads"]
    assert s["live_bytes"] == 0          # every chunk evicted


def test_residency_gather_rows(store, data):
    X, y = data
    td = Dataset(X, label=y, params=BASE_PARAMS).construct(BASE_PARAMS)
    rm = ResidencyManager(store, 1 << 20, prefetch=False)
    idx = np.asarray([0, 511, 512, 1025, N - 1, 7])
    np.testing.assert_array_equal(rm.gather_rows(idx),
                                  td.binned.bins[idx])


# ------------------------------------------------- bitwise streamed pins
def _incore(params, X, y, rounds):
    return engine.train(dict(params), Dataset(X, label=y, params=params),
                        num_boost_round=rounds)


def test_streamed_bitwise_fp32_multichunk(data, store):
    """THE acceptance pin: streamed training at a budget ~40x smaller
    than the dataset's device footprint produces bitwise-identical trees
    to in-core training — messy multi-iteration fp32 gradients, engine
    round loop on both sides."""
    X, y = data
    rounds = 6
    ref = _incore(BASE_PARAMS, X, y, rounds)
    st = train_streamed(_stream_params(), store, num_boost_round=rounds)
    assert st._stream_stats["chunks"] > 1
    assert _trees_only(st) == _trees_only(ref)


@pytest.mark.parametrize("extra,label", [
    ({"use_quantized_grad": True}, "quantized"),
    ({"max_bin": 15}, "packed4"),
    ({"tpu_iter_pack": 4}, "iter_pack_k4"),
    # learning_rate 0.5: GOSS samples from iteration int(1 / 0.5) = 2 on
    ({"data_sample_strategy": "goss", "use_quantized_grad": True,
      "learning_rate": 0.5}, "goss_quantized"),
    ({"use_quantized_grad": True, "max_bin": 15, "tpu_iter_pack": 4},
     "quantized_packed4_pack"),
])
def test_streamed_bitwise_matrix(data, tmp_path, extra, label):
    """Streamed == in-core across the composition matrix: quantized int8
    wire, 4-bit bin packing, iter-pack K=4 (streamed degrades to
    per-round — pack size is scheduling-only since PR 1, so the trees
    must STILL match bitwise), and device GOSS on the quantized wire
    (integer histograms make GOSS's amplified gradients exact; the fp32
    GOSS cell is pinned to 1 ULP in test_streamed_goss_fp32_ulp)."""
    X, y = data
    params = dict(BASE_PARAMS, num_leaves=7, **extra)
    store = dataset_to_shards(Dataset(X, label=y, params=params),
                              str(tmp_path / "m"), rows_per_shard=512,
                              params=params)
    rounds = 4
    ref = _incore(params, X, y, rounds)
    sp = _stream_params(extra={"num_leaves": 7, **extra})
    st = train_streamed(sp, store, num_boost_round=rounds)
    assert st._stream_stats["chunks"] > 1
    assert _trees_only(st) == _trees_only(ref), label


def _assert_structure_ulp(bst, ref, atol=0.0, rtol=3e-7):
    """Tree STRUCTURE (features/bins/children/routing) bitwise, leaf
    values within ~1 f32 ULP — the fp32-GOSS contract: amplified
    (inexact-product) gradients expose XLA's fusion-context-dependent
    rounding inside the split scan's stat reductions, which no
    re-implementation can replay across differently-shaped programs
    (quantized GOSS is bitwise; docs/STREAMING.md)."""
    a, b = bst._gbdt, ref._gbdt
    for k in range(a.num_class):
        for ta, tb in zip(a.dev_models[k], b.dev_models[k]):
            for fld in ("split_feature", "split_bin", "default_left",
                        "is_cat", "left_child", "right_child",
                        "num_leaves", "leaf_count"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ta, fld)),
                    np.asarray(getattr(tb, fld)), err_msg=fld)
            np.testing.assert_allclose(
                np.asarray(ta.leaf_value), np.asarray(tb.leaf_value),
                rtol=rtol, atol=atol)


def test_streamed_goss_fp32_ulp(data, tmp_path):
    """fp32 GOSS: identical structure/routing, leaf values within 1 ULP
    (see _assert_structure_ulp — the quantized GOSS cell in the matrix
    above is the bitwise pin)."""
    X, y = data
    # learning_rate 0.5: two unsampled rounds (goss.hpp), two sampled
    params = dict(BASE_PARAMS, num_leaves=7, data_sample_strategy="goss",
                  learning_rate=0.5)
    store = dataset_to_shards(Dataset(X, label=y, params=params),
                              str(tmp_path / "gf"), rows_per_shard=512,
                              params=params)
    rounds = 4
    ref = _incore(params, X, y, rounds)
    st = train_streamed(_stream_params(extra={"num_leaves": 7,
                                              "learning_rate": 0.5,
                                              "data_sample_strategy":
                                              "goss"}),
                        store, num_boost_round=rounds)
    _assert_structure_ulp(st, ref)


def test_streamed_goss_residency_mode(data, tmp_path):
    """Gradient-based residency: only the device-GOSS sampled slice is
    resident per iteration (compact gather + routing sweep); trees match
    in-core GOSS training bitwise on the (non-stochastic) quantized wire
    and to 1 ULP on fp32."""
    X, y = data
    params = dict(BASE_PARAMS, num_leaves=7, learning_rate=0.5,
                  data_sample_strategy="goss",
                  use_quantized_grad=True, stochastic_rounding=False)
    store = dataset_to_shards(Dataset(X, label=y, params=params),
                              str(tmp_path / "g"), rows_per_shard=512,
                              params=params)
    rounds = 4
    ref = _incore(params, X, y, rounds)
    sp = _stream_params(extra={"num_leaves": 7, "learning_rate": 0.5,
                               "data_sample_strategy": "goss",
                               "use_quantized_grad": True,
                               "stochastic_rounding": False,
                               "tpu_stream_residency": "goss"},
                        budget=0.1)
    sds = StreamDataset(store, params=sp)
    bst = Booster(params=sp, train_set=sds)
    tr = StreamTrainer(bst, store)
    assert tr.residency == "goss"
    for _ in range(rounds):
        tr.train_round()
    tr.close()
    _assert_structure_ulp(bst, ref)
    # the sampled slice really is the resident set: compact bytes cover
    # top_rate+other_rate of the rows, far under the full matrix
    assert 0 < tr.goss_resident_bytes < N * F


def test_streamed_degrade_reasons(data, store):
    """Unsupported compositions refuse with a clear reason instead of
    silently diverging."""
    X, y = data
    sp = _stream_params(extra={"linear_tree": True})
    with pytest.raises(ValueError, match="linear trees"):
        train_streamed(sp, store, num_boost_round=2)


# ----------------------------------------------------- budget via census
def test_budget_respected_live_buffer_census(data, store):
    """The residency invariant against the PR-10 live-buffer census: while
    a sweep holds a chunk, the census sees streaming buffers totalling at
    most the budget, and the FULL (N, F) matrix appears nowhere."""
    import gc

    from lightgbm_tpu.telemetry import live_buffer_census

    def _shape_bytes(census, shape):
        return sum(g["bytes"] for g in census["groups"]
                   if g["shape"] == shape)

    budget = int(TINY_BUDGET_MB * 2 ** 20)
    gc.collect()   # drop earlier tests' dead boosters from the live set
    with ResidencyManager(store, budget) as rm:
        chunk_shape = [rm.plan.chunk_rows, rm.plan.cols]
        base = live_buffer_census(top=200)
        base_chunk = _shape_bytes(base, chunk_shape)
        base_full = _shape_bytes(base, [N, F])
        for _ci, _lo, _hi, _arr in rm.sweep():
            census = live_buffer_census(top=200)
            stream_bytes = _shape_bytes(census, chunk_shape) - base_chunk
            assert 0 < stream_bytes <= budget
            # the full (N, F) matrix never lands on the device
            assert _shape_bytes(census, [N, F]) == base_full
    # and end-to-end training never exceeded it either (manager accounting)
    st = train_streamed(_stream_params(), store, num_boost_round=2)
    assert st._stream_stats["peak_bytes"] <= budget


# --------------------------------------------------- SIGKILL resume pin
_KILL_CHILD = r"""
import os, sys
sys.path.insert(0, os.environ["LGB_REPO"])
import _hermetic
_hermetic.force_cpu(1)
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.stream import dataset_to_shards, train_streamed

rng = np.random.RandomState(0)
X = rng.rand(3072, 8)
y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float64)
params = dict(objective="binary", num_leaves=7, seed=3, verbosity=-1,
              min_data_in_leaf=5, checkpoint_interval=4,
              checkpoint_keep=3, checkpoint_dir=sys.argv[1],
              tpu_stream_budget_mb=0.02)
store_dir = "store"
if not os.path.exists(os.path.join(store_dir, "manifest.json")):
    dataset_to_shards(lgb.Dataset(X, label=y, params=params), store_dir,
                      rows_per_shard=512, params=params)
resume = sys.argv[3] if len(sys.argv) > 3 else None
bst = train_streamed(params, store_dir, num_boost_round=12,
                     resume_from=resume)
bst.save_model(sys.argv[2])
"""


def _run_child(cwd, args, fault=None, timeout=420):
    from lightgbm_tpu.resilience import faults
    env = {k: v for k, v in os.environ.items()
           if k not in (faults.ENV_VAR, "JAX_PLATFORMS", "XLA_FLAGS")}
    env["LGB_REPO"] = REPO
    if fault:
        env[faults.ENV_VAR] = fault
    os.makedirs(cwd, exist_ok=True)
    return subprocess.run([sys.executable, "-c", _KILL_CHILD, *args],
                          env=env, cwd=cwd, capture_output=True, text=True,
                          timeout=timeout)


def test_sigkill_mid_stream_resume_byte_identical(tmp_path):
    """A continual trainer SIGKILLed mid-stream (fault seam, right after
    round 10 commits) resumes from the last checkpoint and the final
    model FILE is byte-identical to the uninterrupted run's."""
    from lightgbm_tpu.resilience import checkpoint
    golden = str(tmp_path / "golden.txt")
    resumed = str(tmp_path / "resumed.txt")
    cwd_full, cwd_kill = str(tmp_path / "full"), str(tmp_path / "kill")

    p = _run_child(cwd_full, ["ck", golden])
    assert p.returncode == 0, p.stderr[-2000:]
    p = _run_child(cwd_kill, ["ck", str(tmp_path / "never.txt")],
                   fault="kill_after_iter:10")
    assert p.returncode == -signal.SIGKILL, (p.returncode, p.stderr[-2000:])
    assert not os.path.exists(str(tmp_path / "never.txt"))
    assert [it for it, _p in checkpoint.list_snapshots(
        os.path.join(cwd_kill, "ck"))] == [8, 4]
    p = _run_child(cwd_kill, ["ck", resumed, "ck"])
    assert p.returncode == 0, p.stderr[-2000:]
    with open(golden, "rb") as a, open(resumed, "rb") as b:
        assert a.read() == b.read()


# ----------------------------------------------- continuation / continual
def test_streamed_continuation_matches_engine(data, store, tmp_path):
    """init_model continuation parity: the streamed continuation's init
    fold (bin-space f64 routing) reproduces engine.train's raw-space fold
    bitwise, so the continued trees match too."""
    X, y = data
    r1, r2 = 4, 3
    ref1 = _incore(BASE_PARAMS, X, y, r1)
    ref2 = engine.train(dict(BASE_PARAMS),
                        Dataset(X, label=y, params=BASE_PARAMS),
                        num_boost_round=r2, init_model=ref1)
    st1 = train_streamed(_stream_params(), store, num_boost_round=r1)
    st2 = train_streamed(_stream_params(), store, num_boost_round=r2,
                         init_model=st1)
    assert _trees_only(st2) == _trees_only(ref2)


def test_continual_session_ingest_train_refit(data, tmp_path):
    X, y = data
    params = dict(BASE_PARAMS, num_leaves=7)
    st = dataset_to_shards(Dataset(X, label=y, params=params),
                           str(tmp_path / "c"), rows_per_shard=512,
                           params=params)
    sess = ContinualSession(st, _stream_params(extra={"num_leaves": 7}))
    m1 = sess.train(3)
    assert m1._gbdt.iter_ == 3
    X2, y2 = _data(seed=5, n=600)
    sess.ingest(X2, y2)
    assert sess.store.num_data == N + 600
    m2 = sess.train(2, continue_training=True)
    # the chained model predicts with base + own trees
    pred = m2.predict(X[:64], raw_score=True)
    assert np.isfinite(pred).all()
    assert m2._gbdt.base_model is not None
    m3 = sess.train(3, continue_training=False)
    r = refit_streamed(m3, sess.store, decay_rate=0.5)
    assert r._gbdt._pred_version == m3._gbdt._pred_version + 1
    # structures identical, leaf values moved
    assert (np.asarray(r._gbdt.dev_models[0][0].split_feature)
            == np.asarray(m3._gbdt.dev_models[0][0].split_feature)).all()


def test_refit_streamed_matches_host_refit(data, tmp_path):
    """Streamed (per-shard) refit == the host refit path over the same
    rows: same leaf sums, same decay blend."""
    X, y = data
    params = dict(BASE_PARAMS, num_leaves=7)
    st = dataset_to_shards(Dataset(X, label=y, params=params),
                           str(tmp_path / "r"), rows_per_shard=512,
                           params=params)
    bst = _incore(params, X, y, 3)
    from lightgbm_tpu.refit import refit_booster
    want = refit_booster(bst, X, y, 0.7, params)
    got = refit_streamed(bst, st, decay_rate=0.7)
    for t_w, t_g in zip(want._gbdt.models[0], got._gbdt.models[0]):
        np.testing.assert_allclose(t_g.leaf_value, t_w.leaf_value,
                                   rtol=0, atol=0)


# ------------------------------------------------------- serve handoff
def test_continual_train_to_serve_swap_parity(data, tmp_path, monkeypatch):
    """The closing loop: retrain -> publish -> a RUNNING predictor serves
    the new model (zero restart), bitwise-parity with Booster.predict's
    device path (the serve parity contract — the native host traversal
    accumulates f64 and differs in ULPs by design), swaps counted, and
    (same architecture) zero fresh AOT compiles."""
    from lightgbm_tpu import serve
    monkeypatch.setenv("LIGHTGBM_TPU_NATIVE_PREDICT_MAX_ROWS", "0")
    X, y = data
    params = dict(BASE_PARAMS, num_leaves=7)
    st = dataset_to_shards(Dataset(X, label=y, params=params),
                           str(tmp_path / "p"), rows_per_shard=512,
                           params=params)
    cache_dir = str(tmp_path / "aot")
    sp = _stream_params(extra={"num_leaves": 7})
    sess = ContinualSession(st, sp)
    m1 = sess.train(3)
    predictor = serve.Predictor(m1, raw_score=True,
                                compile_cache=cache_dir)
    Xq = X[:256]
    out1 = predictor.predict(Xq)
    np.testing.assert_array_equal(out1, m1.predict(Xq, raw_score=True))
    # fresh retrain over the grown store lands without a restart
    sess.ingest(*_data(seed=21, n=512)[:2])
    m2 = sess.train(3, continue_training=False)
    sess.publish(predictor)
    out2 = predictor.predict(Xq)
    assert predictor.metrics.model_swaps == 1
    np.testing.assert_array_equal(out2, m2.predict(Xq, raw_score=True))
    assert not np.array_equal(out1, out2)
    # zero cold-start: the swapped plan's executables came from the AOT
    # cache (structural identity — same architecture, new values)
    aot = predictor.plan.aot_stats()
    assert aot["compiles"] == 0 and aot["hits"] >= 1


# ------------------------------------------------- satellites: RSS, telemetry
def test_to_shards_free_raw_data_bounds_host_rss(tmp_path):
    """Satellite: ``free_raw_data`` on the streaming path — the raw f64
    matrix is RELEASED once the binned representation exists, so the
    store build adds far less than another raw-matrix copy to host peak
    RSS (pinned as a same-process delta via MemoryTracker, the
    test_inputs idiom)."""
    from lightgbm_tpu.telemetry import MemoryTracker
    n, f = 200_000, 28
    rng = np.random.RandomState(0)
    X = rng.randn(n, f)                      # 44.8 MB raw f64
    y = (X[:, 0] > 0).astype(np.float64)
    ds = Dataset(X, label=y, params=BASE_PARAMS, free_raw_data=True)
    ds.construct(BASE_PARAMS)                # binning paid OUTSIDE the delta
    hwm_ok = MemoryTracker.reset_host_peak()
    base_mb = MemoryTracker.host_peak_rss_mb(use_hwm=hwm_ok)
    store = dataset_to_shards(ds, str(tmp_path / "rss"),
                              rows_per_shard=25_000, params=BASE_PARAMS)
    delta_mb = MemoryTracker.host_peak_rss_mb(use_hwm=hwm_ok) - base_mb
    assert ds.data.size == 0                 # raw matrix released
    assert store.num_data == n
    raw_mb = X.nbytes / 2 ** 20
    # bound: one shard's frame copy + the meta payload + slack — well
    # under another raw-matrix copy (the leak this satellite closes)
    assert delta_mb < raw_mb * 0.75, (delta_mb, raw_mb)


def test_stream_telemetry_events_and_inertness(data, store, tmp_path):
    """Satellite: stream.* telemetry — prefetch hit/stall counters in the
    registry, per-chunk stream.chunk events through the JSONL sink
    (rendered by tools/telemetry_report.py), and tpu_telemetry=off stays
    bitwise-inert (identical trees)."""
    import json as _json
    import subprocess

    from lightgbm_tpu.telemetry import registry
    log = str(tmp_path / "t.jsonl")
    sp = _stream_params(extra={"tpu_telemetry_log": log})
    bst_on = train_streamed(sp, store, num_boost_round=2)
    reg = registry().snapshot()
    hits = reg["counters"].get("stream.prefetch_hits", 0)
    stalls = reg["counters"].get("stream.prefetch_stalls", 0)
    assert hits + stalls > 0
    assert reg["counters"].get("stream.upload_bytes", 0) > 0
    kinds = [(_json.loads(line)).get("kind")
             for line in open(log) if line.strip()]
    assert kinds.count("stream.chunk") > 0
    assert "train.start" in kinds and "train.end" in kinds
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "telemetry_report.py"),
                        log], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "stream chunks" in r.stdout
    # off-mode: same trees (telemetry is host-side observation only)
    bst_off = train_streamed(_stream_params(extra={"tpu_telemetry": "off"}),
                             store, num_boost_round=2)
    assert _trees_only(bst_on) == _trees_only(bst_off)


def test_torn_append_leaves_previous_consistent_store(data, tmp_path):
    """Crash-contract regression: a crash between append_rows' metadata
    write and its manifest write must leave the PREVIOUS consistent
    store (orphaned metadata tail dropped at open), never a brick."""
    X, y = data
    ds = Dataset(X, label=y, params=BASE_PARAMS)
    st = dataset_to_shards(ds, str(tmp_path / "t"), rows_per_shard=512,
                           params=BASE_PARAMS)
    manifest_path = os.path.join(st.path, "manifest.json")
    old_manifest = open(manifest_path, "rb").read()
    X2, y2 = _data(seed=3, n=300)
    append_rows(st, X2, y2)
    # simulate the crash point: meta.npz (and shards) written, manifest
    # rollback to the pre-append generation
    open(manifest_path, "wb").write(old_manifest)
    st2 = ShardedDataset.open(st.path)
    assert st2.num_data == N
    assert len(st2.label) == N
    np.testing.assert_array_equal(st2.label, y)
    # and the store still trains
    bst = train_streamed(_stream_params(), st2, num_boost_round=1)
    assert bst._gbdt.iter_ == 1


def test_residency_sweep_releases_prefetch_on_consumer_raise(store):
    """A consumer that raises mid-sweep must not leak the in-flight
    prefetched chunk's bytes (the live_bytes() <= budget invariant the
    bench witnesses)."""
    budget = int(TINY_BUDGET_MB * 2 ** 20)
    rm = ResidencyManager(store, budget)
    try:
        with pytest.raises(RuntimeError, match="boom"):
            for _ci, _lo, _hi, _arr in rm.sweep():
                raise RuntimeError("boom")
        assert rm.live_bytes() == 0
    finally:
        rm.close()
    assert rm.stats()["live_bytes"] == 0
