"""lightgbm_tpu.resilience: fault-tolerant training & serving (ISSUE 6).

Pins the subsystem's contract:
- checksummed atomic frames detect truncation/bitrot at read time,
- checkpoint/resume produces trees BITWISE-identical to the uninterrupted
  run — incl. bagging/feature_fraction, GOSS, CEGB, linear trees and
  iter-pack K>1 (the commit-boundary snapshot semantics),
- a mid-training SIGKILL (via the fault seam, in a real subprocess)
  resumes from the last committed boundary and the final model FILE is
  byte-identical to the uninterrupted run's (acceptance criterion),
- a corrupted newest generation falls back to the previous one,
- the budgeted watchdog probe returns "wedged" WITHIN its budget under
  the ``wedge_dispatch`` fault (no hang), "live" on a healthy backend,
  and the engine preflight turns a wedged verdict into a clear error,
- serve-side degradation: shed past ``serve_max_queue``, deadline misses
  past ``serve_deadline_ms``, one-shot host-predict fallback on a device
  fault — each counted in ServeMetrics.

Every injected failure goes through resilience/faults.py — the one seam —
so these tests are deterministic: no sleeps hoping for a race, no real
hardware faults required.
"""

import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.resilience import checkpoint, faults, watchdog
from lightgbm_tpu.serialization import (FrameCorruptError, read_frame,
                                        write_atomic_frame)
from lightgbm_tpu.serve import ServeDeadlineError, ServeOverloadError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    """No test inherits another's armed faults (or leaks its own)."""
    faults.install(None)
    yield
    faults.install(None)


def _data(n=500, f=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] + X[:, 1] + 0.2 * rng.rand(n) > 1.1).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 7, "seed": 3, "verbosity": -1,
        "min_data_in_leaf": 5}


def _train(params, X, y, rounds=12, resume_from=None):
    return lgb.train(dict(params), lgb.Dataset(X.copy(), label=y.copy()),
                     num_boost_round=rounds, resume_from=resume_from)


# ----------------------------------------------------- checksummed frames
def test_frame_roundtrip(tmp_path):
    path = str(tmp_path / "frame.bin")
    payload = os.urandom(4096)
    write_atomic_frame(path, payload)
    assert read_frame(path) == payload
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


@pytest.mark.parametrize("damage", ["truncate", "bitflip", "magic"])
def test_frame_damage_detected(tmp_path, damage):
    path = str(tmp_path / "frame.bin")
    write_atomic_frame(path, b"x" * 1000)
    with open(path, "r+b") as fh:
        if damage == "truncate":
            fh.truncate(os.path.getsize(path) // 2)
        elif damage == "bitflip":
            fh.seek(os.path.getsize(path) - 7)
            b = fh.read(1)
            fh.seek(-1, os.SEEK_CUR)
            fh.write(bytes([b[0] ^ 0x40]))
        else:
            fh.write(b"BOGUS")
    with pytest.raises(FrameCorruptError):
        read_frame(path)


# ------------------------------------------------------ checkpoint/resume
@pytest.fixture(scope="module")
def ckpt_run(tmp_path_factory):
    """One 12-round pack-4 run checkpointing every 4 (keep 3): the golden
    model string + its generation chain, shared by the read-only tests."""
    d = str(tmp_path_factory.mktemp("ck"))
    X, y = _data()
    params = dict(BASE, tpu_iter_pack=4, checkpoint_interval=4,
                  checkpoint_keep=3, checkpoint_dir=d)
    full = _train(params, X, y).model_to_string()
    return d, full, params, (X, y)


def test_checkpoint_generations_and_prune(ckpt_run):
    d, _full, _params, _ = ckpt_run
    assert [it for it, _p in checkpoint.list_snapshots(d)] == [12, 8, 4]


def test_checkpoint_prune_keeps_newest(tmp_path):
    d = str(tmp_path / "ck")
    X, y = _data(300, 6)
    params = dict(BASE, tpu_iter_pack=1, checkpoint_interval=2,
                  checkpoint_keep=2, checkpoint_dir=d)
    _train(params, X, y, rounds=6)
    assert [it for it, _p in checkpoint.list_snapshots(d)] == [6, 4]


@pytest.mark.parametrize("extra", [
    {},                                                   # plain, pack K=4
    {"bagging_fraction": 0.7, "bagging_freq": 2,          # device sampling
     "feature_fraction": 0.8},
    {"data_sample_strategy": "goss"},                     # device GOSS
    {"cegb_penalty_feature_coupled": 0.1},                # used-vector state
    {"linear_tree": True},                                # host leaf models
], ids=["plain", "bagging_ff", "goss", "cegb", "linear"])
def test_resume_bitwise_identical(tmp_path, extra):
    """Resume from the iteration-8 snapshot of a 12-round run; the final
    model must be BITWISE identical to the uninterrupted run's."""
    d = str(tmp_path / "ck")
    X, y = _data()
    params = dict(BASE, tpu_iter_pack=4, checkpoint_interval=4,
                  checkpoint_keep=3, checkpoint_dir=d, **extra)
    full = _train(params, X, y).model_to_string()
    snap8 = [p for it, p in checkpoint.list_snapshots(d) if it == 8]
    assert snap8, "no iteration-8 snapshot emitted"
    resumed = _train(params, X, y, resume_from=snap8[0])
    assert resumed.model_to_string() == full


def test_corrupt_latest_falls_back(tmp_path):
    """The ``corrupt_ckpt:latest`` fault tears the newest generation; the
    restore scan must detect it (checksum), warn, and land on gen 8 —
    and a resume from there still reproduces the golden model.  (The
    golden run checkpoints into the SAME directory: the serialized model
    embeds checkpoint_dir in its parameters section, so byte-equality
    needs identical config strings.)"""
    d = str(tmp_path / "ck")
    X, y = _data()
    params = dict(BASE, tpu_iter_pack=4, checkpoint_interval=4,
                  checkpoint_keep=3, checkpoint_dir=d)
    full = _train(params, X, y).model_to_string()
    faults.install("corrupt_ckpt:latest")
    blob, path = checkpoint.load_latest(d)
    assert blob["meta"]["iteration"] == 8
    assert path.endswith("ckpt-00000008.lgtck")
    # the newest generation was physically truncated, not just skipped
    with pytest.raises(FrameCorruptError):
        read_frame(checkpoint.snapshot_path(d, 12))
    faults.install(None)
    resumed = _train(params, X, y, resume_from=d)
    assert resumed.model_to_string() == full


def test_all_generations_corrupt_raises(ckpt_run, tmp_path):
    import shutil
    d0 = ckpt_run[0]
    d = str(tmp_path / "ck")
    shutil.copytree(d0, d)
    for _it, p in checkpoint.list_snapshots(d):
        with open(p, "r+b") as fh:
            fh.truncate(20)
    with pytest.raises(FrameCorruptError):
        checkpoint.load_latest(d)


def test_resume_config_mismatch_rejected(ckpt_run):
    d, _full, params, (X, y) = ckpt_run
    bad = dict(params, num_leaves=15)
    with pytest.raises(ValueError, match="num_leaves"):
        _train(bad, X, y, resume_from=d)


def test_resume_sampling_rate_mismatch_rejected(ckpt_run):
    """Sampling rates are compat keys: the restored RNG streams draw masks
    at whatever rate the resumed config says, so a silent rate change would
    silently diverge the tree stream."""
    d, _full, params, (X, y) = ckpt_run
    bad = dict(params, bagging_fraction=0.5, bagging_freq=1)
    with pytest.raises(ValueError, match="bagging_fraction"):
        _train(bad, X, y, resume_from=d)


def _trees_only(model_str):
    """Strip the serialized parameters section: the resume contract is
    about the TREES, and e.g. a restored learning_rate legitimately
    differs from the booster's configured one in that section."""
    return re.sub(r"parameters:.*?end of parameters", "", model_str,
                  flags=re.DOTALL)


def test_resume_learning_rate_restored_not_rejected(ckpt_run):
    """learning_rate is training STATE (reset_parameter mutates it
    mid-run): a resume with a different configured value restores the
    snapshot's boundary value (warn) and still reproduces the golden
    trees bitwise."""
    d, full, params, (X, y) = ckpt_run
    snap8 = [p for it, p in checkpoint.list_snapshots(d) if it == 8]
    assert snap8, "no iteration-8 snapshot in the golden chain"
    resumed = _train(dict(params, learning_rate=0.31), X, y,
                     resume_from=snap8[0])
    assert _trees_only(resumed.model_to_string()) == _trees_only(full)


def test_resume_early_stopping_bitwise(tmp_path):
    """Resume + early_stopping must reproduce the uninterrupted run: the
    snapshot carries the per-round eval history and the engine replays it
    through the after-callbacks, rebuilding the callback's best/wait
    counters.  Without the replay a resumed run re-baselines 'best' at
    its first post-resume eval and stops at a different iteration."""
    d = str(tmp_path / "ck")
    rng = np.random.RandomState(5)
    X, y = _data(300, 8)
    Xv = rng.rand(60, 8)                          # small noisy valid set:
    yv = (rng.rand(60) > 0.5).astype(np.float64)  # AUC jitters, stop fires
    params = dict(BASE, checkpoint_interval=2, checkpoint_keep=20,
                  checkpoint_dir=d, learning_rate=0.3)

    def run(resume_from=None):
        ds = lgb.Dataset(X.copy(), label=y.copy())
        return lgb.train(
            dict(params), ds, num_boost_round=20, resume_from=resume_from,
            valid_sets=[lgb.Dataset(Xv.copy(), label=yv.copy(),
                                    reference=ds)],
            callbacks=[lgb.early_stopping(3, verbose=False)])

    full = run()
    assert 0 < full.best_iteration < 20, \
        f"fixture must early-stop (best_iteration={full.best_iteration})"
    snaps = checkpoint.list_snapshots(d)
    assert snaps, "no mid-run snapshot emitted before the stop"
    resumed = run(resume_from=snaps[0][1])     # newest pre-stop snapshot
    assert resumed.best_iteration == full.best_iteration
    assert resumed.model_to_string() == full.model_to_string()


def test_resume_reset_parameter_schedule_bitwise(tmp_path):
    """Callbacks see the SAME absolute (iteration, begin, end) stream on
    resume: a full-length reset_parameter learning-rate schedule validates
    and indexes identically, and early_stopping (re)initializes on its
    first firing — the resumed model stays bitwise-identical."""
    d = str(tmp_path / "ck")
    X, y = _data()
    lr = [0.1 - 0.005 * i for i in range(12)]
    params = dict(BASE, checkpoint_interval=4, checkpoint_keep=3,
                  checkpoint_dir=d)

    def run(resume_from=None):
        return lgb.train(
            dict(params), lgb.Dataset(X.copy(), label=y.copy()),
            num_boost_round=12, resume_from=resume_from,
            callbacks=[lgb.reset_parameter(learning_rate=list(lr))])

    full = run().model_to_string()
    snap8 = [p for it, p in checkpoint.list_snapshots(d) if it == 8]
    assert snap8, "no iteration-8 snapshot emitted"
    resumed = run(resume_from=snap8[0])
    assert resumed.model_to_string() == full


def test_checkpoint_interval_warns_on_dart(tmp_path):
    """DART carries per-round host drop state outside the captured set:
    checkpoint_interval must WARN and disable, not snapshot garbage."""
    X, y = _data(300, 6)
    d = str(tmp_path / "ck")
    params = dict(BASE, boosting="dart", checkpoint_interval=1,
                  checkpoint_dir=d)
    _train(params, X, y, rounds=3)
    assert checkpoint.list_snapshots(d) == []


# ----------------------------------------- SIGKILL mid-training (subprocess)
_KILL_CHILD = r"""
import os, sys
sys.path.insert(0, os.environ["LGB_REPO"])
import _hermetic
_hermetic.force_cpu(1)
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.RandomState(0)
X = rng.rand(400, 8)
y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float64)
params = dict(objective="binary", num_leaves=7, seed=3, verbosity=-1,
              min_data_in_leaf=5, tpu_iter_pack=4, checkpoint_interval=4,
              checkpoint_keep=3, checkpoint_dir=sys.argv[1])
resume = sys.argv[3] if len(sys.argv) > 3 else None
bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=12,
                resume_from=resume)
bst.save_model(sys.argv[2])
"""


def _run_child(cwd, args, fault=None, timeout=420):
    """One training child.  ``checkpoint_dir`` is passed RELATIVE and the
    child runs in its own cwd: the serialized model embeds the param
    string, so byte-identical files need identical (relative) paths."""
    env = {k: v for k, v in os.environ.items()
           if k not in (faults.ENV_VAR, "JAX_PLATFORMS", "XLA_FLAGS")}
    env["LGB_REPO"] = REPO
    if fault:
        env[faults.ENV_VAR] = fault
    os.makedirs(cwd, exist_ok=True)
    return subprocess.run([sys.executable, "-c", _KILL_CHILD, *args],
                          env=env, cwd=cwd, capture_output=True, text=True,
                          timeout=timeout)


def test_sigkill_resume_byte_identical_model(tmp_path):
    """THE acceptance criterion: training SIGKILLed mid-run (fault seam,
    right after round 10 commits — past the iteration-8 snapshot, before
    the next boundary) resumes from the last committed checkpoint and the
    final model file is BYTE-identical to the uninterrupted run's."""
    golden = str(tmp_path / "golden.txt")
    resumed = str(tmp_path / "resumed.txt")
    cwd_full, cwd_kill = str(tmp_path / "full"), str(tmp_path / "kill")
    d_kill = os.path.join(cwd_kill, "ck")

    p = _run_child(cwd_full, ["ck", golden])
    assert p.returncode == 0, p.stderr[-2000:]

    p = _run_child(cwd_kill, ["ck", str(tmp_path / "never.txt")],
                   fault="kill_after_iter:10")
    assert p.returncode == -signal.SIGKILL, (p.returncode, p.stderr[-2000:])
    assert not os.path.exists(str(tmp_path / "never.txt"))
    # the crash landed between boundaries: snapshots stop at 8
    assert [it for it, _p in checkpoint.list_snapshots(d_kill)] == [8, 4]

    p = _run_child(cwd_kill, ["ck", resumed, "ck"])
    assert p.returncode == 0, p.stderr[-2000:]
    with open(golden, "rb") as a, open(resumed, "rb") as b:
        assert a.read() == b.read()


# ------------------------------------------------------- backend watchdog
def test_watchdog_wedged_verdict_within_budget():
    """A probe child stalled by ``wedge_dispatch`` must be classified
    wedged AT the budget — never hang past it (acceptance criterion)."""
    t0 = time.time()
    res = watchdog.probe_backend(
        timeout=2.0,
        extra_env={faults.ENV_VAR: "wedge_dispatch:600"})
    elapsed = time.time() - t0
    assert res.verdict == "wedged" and not res.live
    assert res.latency_s >= 2.0 and elapsed < 30.0
    assert "budget" in (res.error or "")


def test_watchdog_live_cpu_probe():
    res = watchdog.probe_backend(platform="cpu")
    assert res.verdict == "live" and res.live
    assert res.backend == "cpu" and res.devices >= 1
    d = res.as_dict()
    assert {"verdict", "backend", "devices", "latency_s",
            "budget_s", "error"} <= set(d)


def test_watchdog_error_verdict():
    res = watchdog.probe_backend(timeout=90.0, platform="bogus_device")
    assert res.verdict == "error" and not res.live
    assert res.error


def test_watchdog_cli_exit_codes(monkeypatch, capsys):
    monkeypatch.setenv(faults.ENV_VAR, "wedge_dispatch:600")
    rc = watchdog.main(["--timeout", "2"])
    assert rc == 2
    import json
    assert json.loads(capsys.readouterr().out)["verdict"] == "wedged"


def test_engine_preflight_wedged_raises(monkeypatch):
    """LIGHTGBM_TPU_WATCHDOG=1 turns a wedged backend into a clear crash
    BEFORE the trainer touches the device — within the probe budget."""
    monkeypatch.setenv(watchdog.WATCHDOG_ENV, "1")
    monkeypatch.setenv(faults.ENV_VAR, "wedge_dispatch:600")
    # the test process initialised its backend long ago; stand in for a
    # fresh one, where the preflight is allowed to probe
    monkeypatch.setattr(watchdog, "_backend_initialised", lambda: False)
    X, y = _data(100, 4)
    t0 = time.time()
    with pytest.raises(watchdog.BackendWedgedError, match="wedged"):
        lgb.train(dict(BASE, tpu_probe_timeout=1.5),
                  lgb.Dataset(X, label=y), num_boost_round=1)
    assert time.time() - t0 < 30.0


def test_engine_preflight_refuses_once_backend_is_live(monkeypatch, capsys):
    """One process per chip: a process whose backend is initialised holds
    the device, so an armed preflight must NOT start a probe child (whose
    failure to take the chip would read as "wedged") — it says so and
    training proceeds."""
    import jax
    jax.devices()
    monkeypatch.setenv(watchdog.WATCHDOG_ENV, "1")
    monkeypatch.setattr(
        watchdog, "probe_backend",
        lambda *a, **k: pytest.fail("probe child started under a live "
                                    "in-process backend"))
    assert watchdog.preflight({"tpu_probe_timeout": 1.0}) is None
    out = capsys.readouterr()
    assert "not probing from a second process" in out.out + out.err


def test_unknown_fault_name_ignored():
    faults.install("no_such_seam:1,wedge_dispatch:0")
    assert set(faults.spec()) == {"wedge_dispatch"}
    assert not faults.active("kill_after_iter")


# --------------------------------------------- serve graceful degradation
@pytest.fixture(scope="module")
def served():
    X, y = _data(400, 8, seed=1)
    bst = lgb.train(dict(BASE, serve_max_queue=7, serve_deadline_ms=123.0),
                    lgb.Dataset(X, label=y), num_boost_round=5)
    return bst.serving_predictor(), X


def test_serve_host_fallback_on_device_fault(served):
    """The request that sees a device fault is answered from the host
    mirror — same scores, counted — and the NEXT request uses the device
    again (one-shot, not a permanent downgrade)."""
    pred, X = served
    base = pred.predict(X[:16])
    m0 = pred.metrics_snapshot()
    faults.install("serve_device_error:1")
    out = pred.predict(X[:16])
    after = pred.predict(X[:16])     # 2nd dispatch: fault seam already spent
    m1 = pred.metrics_snapshot()
    np.testing.assert_allclose(out, base, atol=1e-6)
    np.testing.assert_array_equal(after, base)
    assert m1["device_faults"] == m0["device_faults"] + 1
    assert m1["host_fallbacks"] == m0["host_fallbacks"] + 1


def test_serve_input_error_not_routed_to_fallback(served):
    """A caller input error (wrong feature count) is the caller's to see:
    it must raise ValueError, not be silently answered by the host mirror
    or counted as a device fault."""
    pred, X = served
    m0 = pred.metrics_snapshot()
    with pytest.raises(ValueError, match="plan expects"):
        pred.predict(X[:4, :-1])
    m1 = pred.metrics_snapshot()
    assert m1["device_faults"] == m0["device_faults"]
    assert m1["host_fallbacks"] == m0["host_fallbacks"]


def test_serve_host_fallback_multiclass_softmax():
    """The numpy output-transform mirror must match the device softmax."""
    rng = np.random.RandomState(2)
    X = rng.rand(300, 5)
    y = rng.randint(0, 3, 300).astype(np.float64)
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 7, "verbosity": -1, "seed": 3},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    pred = bst.serving_predictor()
    base = pred.predict(X[:8])
    faults.install("serve_device_error:1")
    out = pred.predict(X[:8])
    np.testing.assert_allclose(out, base, atol=1e-6)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=1e-6)


def test_batcher_defaults_from_config(served):
    pred, _X = served
    mb = pred.batcher()
    try:
        assert mb.max_queue == 7
        assert mb.deadline_s == pytest.approx(0.123)
    finally:
        mb.close()


def test_serve_shed_past_max_queue(served):
    """With the dispatch wedged slow and a 2-deep queue, submits past the
    bound must shed with ServeOverloadError and be counted."""
    pred, X = served
    shed0 = pred.metrics_snapshot()["shed"]
    faults.install("wedge_dispatch:0.3")
    # deadline_ms=0 explicitly: the fixture model's serve_deadline_ms=123
    # would otherwise expire the queued-behind-the-wedge requests we are
    # asserting resolve
    mb = pred.batcher(max_batch=1, max_wait_ms=1.0, max_queue=2,
                      deadline_ms=0.0)
    futs, sheds = [], 0
    try:
        for i in range(10):
            try:
                futs.append(mb.submit(X[i]))
            except ServeOverloadError:
                sheds += 1
        assert sheds >= 1
        for f in futs:          # every ADMITTED request still resolves
            assert f.result(timeout=30).shape == (1,)
    finally:
        faults.install(None)
        mb.close()
    assert pred.metrics_snapshot()["shed"] == shed0 + sheds


def test_serve_deadline_miss_failed_not_dispatched(served):
    """Requests queued past their deadline while a slow dispatch holds the
    worker are failed with ServeDeadlineError (and counted) instead of
    dispatched late; the in-flight request itself still succeeds."""
    pred, X = served
    miss0 = pred.metrics_snapshot()["deadline_misses"]
    faults.install("wedge_dispatch:0.25")
    mb = pred.batcher(max_batch=8, max_wait_ms=1.0, deadline_ms=40.0)
    try:
        first = mb.submit(X[0])
        time.sleep(0.05)         # worker has picked it up and is dispatching
        late = [mb.submit(X[i]) for i in (1, 2)]
        assert first.result(timeout=30).shape == (1,)
        for f in late:
            with pytest.raises(ServeDeadlineError):
                f.result(timeout=30)
    finally:
        faults.install(None)
        mb.close()
    assert pred.metrics_snapshot()["deadline_misses"] == miss0 + 2


def test_serve_expired_only_batch_skips_dispatch(served):
    """A flush whose EVERY request already expired must not dispatch at
    all — padding the device with dead work only delays live requests."""
    pred, X = served
    sizes = []
    orig = pred.predict
    pred.predict = lambda Xb, _record=True, **kw: (
        sizes.append(Xb.shape[0]) or orig(Xb, _record=_record, **kw))
    try:
        faults.install("wedge_dispatch:0.3")
        mb = pred.batcher(max_batch=8, max_wait_ms=1.0, deadline_ms=40.0)
        try:
            first = mb.submit(X[0])
            time.sleep(0.05)     # worker is inside the wedged dispatch
            late = [mb.submit(X[i]) for i in (1, 2)]
            assert first.result(timeout=30).shape == (1,)
            for f in late:
                with pytest.raises(ServeDeadlineError):
                    f.result(timeout=30)
        finally:
            faults.install(None)
            mb.close()
    finally:
        pred.predict = orig
    assert sizes == [1], f"expired-only batch was dispatched: {sizes}"
