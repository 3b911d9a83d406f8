"""Training entry points: ``train`` and ``cv``.

Reference: ``python-package/lightgbm/engine.py`` (``train:109`` — the iteration
loop at ``engine.py:309-322``; ``cv:611`` with stratified/group folds).
"""

from __future__ import annotations

import copy
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from . import callback as callback_mod
from . import telemetry as telemetry_mod
from .basic import Booster, Dataset
from .callback import CallbackEnv, EarlyStopException
from .resilience import faults


def train(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    valid_sets: Optional[Sequence[Dataset]] = None,
    valid_names: Optional[Sequence[str]] = None,
    feval: Optional[Callable] = None,
    init_model: Optional[Union[str, Booster]] = None,
    keep_training_booster: bool = False,
    callbacks: Optional[List[Callable]] = None,
    resume_from: Optional[str] = None,
) -> Booster:
    """Train a booster (reference ``engine.train``).

    ``resume_from`` continues training from a resilience checkpoint (a
    snapshot file or a checkpoint directory — the newest valid generation
    wins); the resumed run's trees are bitwise-identical to the
    uninterrupted run's (docs/ROBUSTNESS.md).  ``checkpoint_interval`` in
    ``params`` emits such snapshots every N committed rounds, at iter-pack
    commit boundaries."""
    # Backend watchdog preflight (opt-in LIGHTGBM_TPU_WATCHDOG=1): classify
    # a wedged accelerator in a budgeted subprocess BEFORE this process
    # touches the device — a clear error instead of an indefinite hang.
    from .resilience.watchdog import preflight
    preflight(params)
    # Callable objective (reference: params["objective"] may be a function
    # (grad, hess) = fobj(preds, train_data) since lightgbm 4.x).
    fobj = None
    if callable(params.get("objective")):
        fobj = params["objective"]
        params = {**params, "objective": "custom"}
    params = copy.deepcopy(params)
    if "num_iterations" in params or "num_boost_round" in params:
        num_boost_round = int(params.pop("num_boost_round",
                              params.pop("num_iterations", num_boost_round)))
    # early stopping via params (reference: _ConfigAliases handling).
    early_stopping_rounds = None
    for alias in ("early_stopping_round", "early_stopping_rounds",
                  "early_stopping", "n_iter_no_change"):
        if params.get(alias):
            early_stopping_rounds = int(params[alias])
    first_metric_only = bool(params.get("first_metric_only", False))
    es_min_delta = float(params.get("early_stopping_min_delta", 0.0))

    valid_sets = list(valid_sets or [])
    names = list(valid_names or [])
    valid_pairs = []
    for i, vs in enumerate(valid_sets):
        if vs is train_set:
            continue
        nm = names[i] if i < len(names) else f"valid_{i}"
        valid_pairs.append((nm, vs))

    # Training continuation (reference boosting.cpp:34-59 + engine.py init_model
    # handling): load the base model, replay its raw predictions into every
    # dataset's init_score, and keep its trees for saving/prediction.
    base = None
    if init_model is not None:
        from .serialization import LoadedModel, load_model_string
        if isinstance(init_model, Booster):
            base = load_model_string(init_model.model_to_string())
        elif isinstance(init_model, LoadedModel):
            base = init_model
        else:
            with open(init_model) as fh:
                base = load_model_string(fh.read())

        def _fold_init(ds: Dataset) -> Dataset:
            # Work on a shallow copy: the caller's Dataset must keep its own
            # init_score (re-running train() on it would otherwise compound).
            if getattr(ds, "_text_path", None) is not None:
                ds.construct(params)   # load raw rows before predicting
            if not getattr(ds, "data", np.zeros(0)).size:
                raise ValueError(
                    "init_model continuation needs raw feature data to "
                    "fold base predictions; binary dataset caches hold "
                    "only binned columns — pass arrays or a text file")
            out = copy.copy(ds)
            from .binning import _is_sparse, predict_dense_chunks
            if _is_sparse(ds.data):
                pred = predict_dense_chunks(base.predict_raw, ds.data)
            else:
                pred = np.asarray(base.predict_raw(ds.data), np.float64)
            if ds.init_score is not None:
                pred = pred + np.asarray(ds.init_score,
                                         np.float64).reshape(pred.shape)
            out.init_score = pred
            out._train_data = None  # re-construct with the new init_score
            return out
        orig_train = train_set
        train_set = _fold_init(train_set)
        new_pairs = []
        for nm, vs in valid_pairs:
            vc = _fold_init(vs)
            if vc.reference is orig_train:
                vc.reference = train_set
            new_pairs.append((nm, vc))
        valid_pairs = new_pairs

    booster = Booster(params=params, train_set=train_set,
                      valid_sets=valid_pairs, base_model=base)

    cbs = list(callbacks or [])
    if early_stopping_rounds is not None and valid_pairs:
        cbs.append(callback_mod.early_stopping(
            early_stopping_rounds, first_metric_only=first_metric_only,
            verbose=params.get("verbosity", 1) > 0,
            min_delta=es_min_delta))
    cbs_before = [cb for cb in cbs if getattr(cb, "before_iteration", False)]
    cbs_after = [cb for cb in cbs if not getattr(cb, "before_iteration", False)]
    cbs_before.sort(key=lambda cb: getattr(cb, "order", 0))
    cbs_after.sort(key=lambda cb: getattr(cb, "order", 0))

    # Periodic model snapshots (reference gbdt.cpp:250-254 snapshot_freq:
    # saves "<output_model>.snapshot_iter_<n>" during training).  Resolved
    # through Config so aliases (save_period, model_out, ...) apply.
    snapshot_freq = booster.cfg.snapshot_freq
    snapshot_base = booster.cfg.output_model or "LightGBM_model.txt"

    # Eval cadence (callback.py contract): a callback may declare the period
    # at which it consumes metrics via ``cb.eval_period`` (default 1); the
    # engine skips metric computation — and its host transfer — on rounds
    # nothing consumes, and the pack plan below aligns to the cadence.
    # eval_period <= 0 marks a callback that never consumes metrics (e.g.
    # log_evaluation(period=0), the documented way to silence logging).
    cb_periods = [p for p in (int(getattr(cb, "eval_period", 1))
                              for cb in cbs_after) if p > 0]
    if feval is not None:
        cb_periods.append(1)
    eval_period = min(cb_periods) if cb_periods else None

    def _round_needs_eval(it: int) -> bool:
        return any((it + 1) % p == 0 for p in cb_periods)

    # Iteration packing (docs/ITER_PACK.md): scan K boosting rounds into ONE
    # device dispatch when nothing demands per-round host access.  Per-round
    # param resets (before-callbacks), snapshots, custom objectives and
    # training-score consumers (feval / training metric — mid-pack train
    # scores do not exist on the host) pin the per-round path; everything
    # else is the booster's pack plan (auto-degrade list lives there).
    needs_train_scores = feval is not None or (
        bool(cbs_after) and booster.cfg.is_provide_training_metric)
    pack_k, use_pack = 1, False
    if (fobj is None and not cbs_before and snapshot_freq <= 0
            and not needs_train_scores):
        pack_k, use_pack = booster._gbdt.iter_pack_plan(
            num_boost_round, eval_period)
    if use_pack and num_boost_round % pack_k:
        # A trailing remainder pack would compile a SECOND scan program
        # (the pack cache keys on K).  Pack size is scheduling-only (models
        # are bitwise identical across K), so snap to a divisor of the
        # round count when one exists nearby; keep the remainder scheme
        # when the only divisors are tiny (a prime round count must not
        # degrade to per-round dispatching).
        div = max((d for d in range(1, pack_k + 1)
                   if num_boost_round % d == 0), default=1)
        if div >= max(pack_k // 2, 2):
            pack_k = div

    # best_iteration counts over the COMBINED model (base trees first) so
    # Booster.predict's num_iteration slicing keeps the full base ensemble.
    n_base = base.iter_ if base is not None else 0

    # Telemetry session (telemetry/, docs/OBSERVABILITY.md): arms the
    # process-wide span switch and the JSONL event sink from the config,
    # owns the optional first-N-iterations jax.profiler capture, and
    # closes what it opened when training ends.  Host-side only — with
    # tpu_telemetry=off every emit below is a no-op and the compiled
    # training programs are bitwise-identical either way.
    tel = telemetry_mod.train_session(booster.cfg)

    # Checkpoint/resume (docs/ROBUSTNESS.md).  Snapshots are emitted only
    # at iter-pack commit boundaries — mid-pack, scores already include
    # uncommitted rounds — so with packing the interval is a floor: the
    # snapshot lands at the first boundary at/after each interval multiple.
    start_it = 0
    # Per-round eval history, recorded while checkpointing (and carried in
    # every snapshot): after-callback closure state — early_stopping's
    # best/wait counters, record_evaluation's dict — is DERIVED from these
    # values, so a resumed run replays them below instead of trying to
    # pickle user callback closures.
    booster._ckpt_eval_history = []
    # Training-health sentinel (docs/ROBUSTNESS.md, resilience/health.py):
    # tpu_health_policy != off arms in-dispatch NaN/Inf/overflow guards, a
    # loss-divergence detector over the per-round eval history and —
    # under "rollback" — checkpoint-backed auto-recovery.
    from .resilience import health as health_mod
    sentinel = None
    if booster.cfg.tpu_health_policy != "off":
        sentinel = health_mod.TrainingHealthSentinel(booster.cfg)
    booster._health_report = (health_mod.off_report() if sentinel is None
                              else sentinel.report())
    if resume_from is not None:
        from .resilience import checkpoint as checkpoint_mod
        try:
            start_it = checkpoint_mod.restore(booster, resume_from)
            # Recovery generation (tpu_health_recovery_salt > 0): the SAME
            # lr-backoff + key-refold transformation the in-process
            # rollback applies — which is what makes a fresh resume with
            # the same salt reproduce the recovered run's trees bitwise.
            health_mod.apply_recovery(booster,
                                      booster.cfg.tpu_health_recovery_salt)
            try:
                for it_h, evals_h in booster._ckpt_eval_history:
                    if it_h >= start_it:
                        continue
                    for cb in cbs_after:
                        cb(CallbackEnv(booster, params, it_h, 0,
                                       num_boost_round, evals_h))
            except EarlyStopException as e:
                # cannot fire for rounds the original run trained past (a
                # stop breaks the loop before the next snapshot), but
                # handle it exactly as _fire_after would, defensively
                booster.best_iteration = e.best_iteration + 1 + n_base
                booster.best_score = e.best_score
                tel.close()   # this session's sink must not outlive it
                return booster
        except BaseException:
            # a failed restore/recovery/replay must not strand the sink
            tel.close()
            raise
    ckpt_interval = booster.cfg.checkpoint_interval
    if ckpt_interval > 0 and not booster._gbdt._supports_checkpoint:
        from .utils.log import Log
        Log.warning(
            f"checkpoint_interval is ignored for boosting="
            f"{booster.cfg.boosting}: per-round host state is not captured")
        ckpt_interval = 0
    ckpt_dir = booster.cfg.checkpoint_dir or f"{snapshot_base}.ckpt"
    last_ckpt = [start_it]
    if (sentinel is not None and sentinel.policy == "rollback"
            and ckpt_interval <= 0):
        from .utils.log import Log
        Log.warning(
            "tpu_health_policy=rollback without checkpoint_interval>0: "
            "there will be no checkpoint to roll back to, so a tripped "
            "sentinel escalates straight to HealthHaltError")

    def _maybe_checkpoint(done_it: int) -> Optional[float]:
        """Snapshot when the cadence is due; returns the write duration in
        seconds (None when no snapshot was due) — the ``checkpoint_s``
        field of the round's ``train.iter`` event."""
        if ckpt_interval <= 0 \
                or done_it // ckpt_interval <= last_ckpt[0] // ckpt_interval:
            return None
        from .resilience import checkpoint as checkpoint_mod
        t0 = time.perf_counter()
        checkpoint_mod.save_snapshot(booster, ckpt_dir,
                                     keep=booster.cfg.checkpoint_keep)
        dt = time.perf_counter() - t0
        last_ckpt[0] = done_it
        tel.emit("train.checkpoint", iteration=done_it, dir=ckpt_dir,
                 seconds=round(dt, 6))
        return dt

    # evals the sentinel already computed for a round (keyed by 0-based
    # iteration), reused by _fire_after so arming the sentinel never
    # doubles the per-round eval cost.  Only populated when feval is None
    # (the sentinel's _evals() carries no feval rows).
    sentinel_evals: Dict[int, list] = {}

    def _fire_after(it: int) -> bool:
        """Eval + after-callbacks for round ``it``; True = early stop."""
        if not _round_needs_eval(it):
            return False
        evals = sentinel_evals.pop(it, None)
        if evals is None:
            evals = booster._evals(feval)
        # no after-callbacks -> nothing to replay on resume: skip the
        # history (each snapshot re-pickles the whole list, so for long
        # runs this is the difference between O(1) and O(rounds) extra
        # bytes per generation)
        if ckpt_interval > 0 and cbs_after:
            booster._ckpt_eval_history.append((it, evals))
        try:
            for cb in cbs_after:
                # begin_iteration stays 0 on resume: callbacks see the same
                # absolute (iteration, begin, end) stream as the
                # uninterrupted run, so reset_parameter schedules index the
                # same values and the bitwise-resume contract holds
                # (early_stopping self-initializes on its first firing).
                cb(CallbackEnv(booster, params, it, 0,
                               num_boost_round, evals))
        except EarlyStopException as e:
            booster.best_iteration = e.best_iteration + 1 + n_base
            booster.best_score = e.best_score
            return True
        return False

    # ---- health sentinel hooks (docs/ROBUSTNESS.md health section) ----
    rollbacks_done = [0]

    def _health_check(done_it: int) -> bool:
        """Observe the just-committed round ``done_it`` (1-based count of
        committed rounds).  Returns True when the engine must roll back;
        warn logs and continues; halt (and an exhausted rollback budget)
        raises :class:`~.resilience.health.HealthHaltError`.  Runs BEFORE
        the round's after-callbacks so halt/rollback policies never feed a
        diverged metric into early-stopping state."""
        if sentinel is None:
            return False
        hv = booster._gbdt.consume_health()
        evals = None
        if valid_pairs or booster.cfg.is_provide_training_metric:
            evals = booster._evals()
            if feval is None:
                sentinel_evals.clear()
                sentinel_evals[done_it - 1] = evals
            if use_pack and evals:
                # Mid-pack, train scores already include the WHOLE pack
                # (train_pack committed scores2 up front), so the training
                # metric is the same end-of-pack value at every commit —
                # feeding it to the detector would trip loss_stagnation
                # on healthy runs.  Valid scores DO advance per commit
                # (_store_tree), so only training rows are dropped.
                evals = [e for e in evals if e[0] != "training"]
        trip = sentinel.observe_round(done_it, hv, evals)
        if trip is None:
            return False
        from .utils.log import Log
        if sentinel.policy == "warn":
            Log.warning(f"health sentinel tripped: {trip} (policy=warn, "
                        "training continues)")
            return False
        if sentinel.policy == "halt":
            sentinel.note_halt()
            booster._health_report = sentinel.report()
            raise health_mod.HealthHaltError(
                f"training halted by the health sentinel: {trip} "
                "(tpu_health_policy=halt)", booster)
        return True   # rollback

    def _do_rollback() -> int:
        """Restore the newest valid checkpoint in-process and apply the
        next recovery generation (lr backoff + key refold).  Returns the
        iteration training resumes at."""
        trip = sentinel.trips[-1]
        rollbacks_done[0] += 1
        cap = booster.cfg.tpu_health_max_rollbacks
        if rollbacks_done[0] > cap:
            sentinel.note_halt()
            booster._health_report = sentinel.report()
            raise health_mod.HealthHaltError(
                f"health sentinel: {trip} — tpu_health_max_rollbacks="
                f"{cap} recovery attempts exhausted", booster)
        from .resilience import checkpoint as checkpoint_mod
        from .serialization import FrameCorruptError
        try:
            start = checkpoint_mod.restore(booster, ckpt_dir)
        except (FileNotFoundError, FrameCorruptError) as e:
            sentinel.note_halt()
            booster._health_report = sentinel.report()
            raise health_mod.HealthHaltError(
                f"health sentinel: {trip} — rollback impossible "
                f"({e})", booster) from e
        salt = booster.cfg.tpu_health_recovery_salt + rollbacks_done[0]
        health_mod.apply_recovery(booster, salt)
        sentinel.note_rollback(start, salt)
        tel.emit("train.rollback", restored_iteration=start, salt=salt,
                 trip=str(trip),
                 rollbacks=f"{rollbacks_done[0]}/{cap}")
        sentinel_evals.clear()   # cached evals refer to discarded rounds
        # checkpoint cadence and eval-history replay state rewind with the
        # restore; after-callbacks are NOT replayed here (they already saw
        # rounds <= start in this process — docs/ROBUSTNESS.md).
        last_ckpt[0] = start
        return start

    it = start_it
    t_train0 = time.perf_counter()
    tel.emit(
        "train.start", num_boost_round=num_boost_round, start_iteration=it,
        objective=booster.cfg.objective, boosting=booster.cfg.boosting,
        num_class=booster._gbdt.num_class,
        rows=booster._gbdt.train_data.num_data,
        features=booster._gbdt.train_data.num_features,
        packed=use_pack, pack_size=pack_k if use_pack else 1,
        pack_degrade_reason=booster._gbdt.iter_pack_degrade_reason(),
        health_policy=booster.cfg.tpu_health_policy,
        checkpoint_interval=ckpt_interval,
        valid_sets=[nm for nm, _ in valid_pairs])
    tel.maybe_start_profile()

    def _emit_iter(done_it: int, pack_size: int,
                   ckpt_s: Optional[float]) -> None:
        """One ``train.iter`` event per COMMITTED round — a view of the
        iteration's record (telemetry/iters.py: the wall time to the next
        iteration, the part of it before the program was enqueued, CPU,
        switches, faults, compiles; a pack's amortised per round), written
        when the next iteration closes that record — plus the checkpoint
        write and the health verdict so far."""
        tel.emit_iter(done_it, pack_size,
                      checkpoint_s=(None if ckpt_s is None
                                    else round(ckpt_s, 6)),
                      health=(None if sentinel is None
                              else sentinel.verdict()))
        tel.maybe_stop_profile(done_it - start_it)

    try:
        while it < num_boost_round:
            if use_pack:
                rounds, finished = booster._gbdt.train_pack(
                    min(pack_k, num_boost_round - it))
                committed = 0
                stopped = False
                rollback_due = False
                try:
                    for j, rnd in enumerate(rounds):
                        # Commit one round, then replay its callbacks/eval:
                        # valid scores update per committed tree, so
                        # callbacks observe the SAME per-iteration metric
                        # sequence as the per-round loop (early stopping
                        # fires at the identical iteration).
                        booster._gbdt.commit_round(rnd)
                        committed += 1
                        # fault seam: a mid-training SIGKILL lands right
                        # after a commit, the worst legal place for a crash
                        faults.maybe_kill(it + j + 1)
                        rollback_due = _health_check(it + j + 1)
                        stopped = (not rollback_due) and _fire_after(it + j)
                        _emit_iter(it + j + 1, len(rounds), None)
                        if rollback_due or stopped:
                            break
                finally:
                    # Uncommitted rounds were trained inside the same
                    # dispatch but never observed (mid-pack early stop, a
                    # tripped sentinel, or a callback raising) — drop their
                    # score contributions so a caller who keeps training
                    # from this booster sees consistent state.
                    if committed < len(rounds):
                        booster._gbdt.discard_rounds(rounds[committed:])
                it += committed
                if rollback_due:
                    it = _do_rollback()
                    continue
                if (finished and not stopped and _health_check(it + 1)):
                    # a degenerate stop can BE the failure: a NaN-poisoned
                    # round grows no tree, so the trimmed stopping round's
                    # health vector (surfaced by train_pack) is checked
                    # before the stop is accepted as convergence
                    it = _do_rollback()
                    continue
                if stopped or finished:
                    break
                _maybe_checkpoint(it)
            else:
                for cb in cbs_before:
                    cb(CallbackEnv(booster, params, it, 0,
                                   num_boost_round, None))
                finished = booster.update(fobj=fobj)
                faults.maybe_kill(it + 1)
                if snapshot_freq > 0 and (it + 1) % snapshot_freq == 0:
                    booster.save_model(
                        f"{snapshot_base}.snapshot_iter_{it + 1}")
                if _health_check(it + 1):
                    _emit_iter(it + 1, 1, None)
                    it = _do_rollback()
                    continue
                stopped = _fire_after(it)
                it += 1
                ckpt_s = None
                if not (stopped or finished):
                    ckpt_s = _maybe_checkpoint(it)
                _emit_iter(it, 1, ckpt_s)
                if stopped or finished:
                    break
    finally:
        if sentinel is not None:
            booster._health_report = sentinel.report()
        tel.flush_iters()
        tel.emit("train.end", iterations=int(booster._gbdt.iter_),
                 elapsed_s=round(time.perf_counter() - t_train0, 6),
                 best_iteration=int(booster.best_iteration),
                 health=(None if sentinel is None
                         else sentinel.verdict()),
                 # host-side peak RSS (telemetry/memory.py) — also
                 # published as the memory.host_peak_rss_mb gauge, the
                 # host half of the run's memory accounting
                 host_peak_rss_mb=round(telemetry_mod.host_peak_rss_mb(), 1),
                 spans=tel.span_delta())
        tel.close()
    return booster


def cv(
    params: Dict[str, Any],
    train_set: Dataset,
    num_boost_round: int = 100,
    folds=None,
    nfold: int = 5,
    stratified: bool = True,
    shuffle: bool = True,
    metrics=None,
    seed: int = 0,
    callbacks: Optional[List[Callable]] = None,
    eval_train_metric: bool = False,
    return_cv_booster: bool = False,
) -> Dict[str, List[float]]:
    """K-fold cross-validation (reference ``engine.cv:611``)."""
    params = copy.deepcopy(params)
    if metrics is not None:
        params["metric"] = metrics
    train_set.construct(params)
    X, y = train_set.data, train_set.label
    n = len(y)
    rng = np.random.RandomState(seed)

    group = train_set.group
    if folds is None and group is not None:
        # Query-aware folds: split whole queries (reference _make_n_folds
        # group handling) so ranking objectives keep their query structure.
        nq = len(group)
        bounds = np.concatenate([[0], np.cumsum(group)])
        q_idx = np.arange(nq)
        if shuffle:
            rng.shuffle(q_idx)
        q_parts = np.array_split(q_idx, nfold)
        folds = []
        for i in range(nfold):
            va_q = np.sort(q_parts[i])
            tr_q = np.sort(np.concatenate(
                [p for j, p in enumerate(q_parts) if j != i]))
            va_rows = np.concatenate([np.arange(bounds[q], bounds[q + 1])
                                      for q in va_q])
            tr_rows = np.concatenate([np.arange(bounds[q], bounds[q + 1])
                                      for q in tr_q])
            folds.append((tr_rows, va_rows, group[tr_q], group[va_q]))
        results: Dict[str, List[float]] = {}
        boosters, fold_histories = [], []
        w = train_set.weight
        for tr_idx, va_idx, tr_g, va_g in folds:
            dtr = Dataset(X[tr_idx], label=np.asarray(y)[tr_idx], group=tr_g,
                          weight=None if w is None else w[tr_idx],
                          params=params)
            dva = Dataset(X[va_idx], label=np.asarray(y)[va_idx], group=va_g,
                          weight=None if w is None else w[va_idx],
                          reference=dtr, params=params)
            history: Dict[str, Dict[str, List[float]]] = {}
            cbs = list(callbacks or []) + [callback_mod.record_evaluation(history)]
            bst = train(params, dtr, num_boost_round, valid_sets=[dva],
                        valid_names=["valid"], callbacks=cbs)
            boosters.append(bst)
            fold_histories.append(history.get("valid", {}))
        return _collect_cv(results, fold_histories, boosters,
                           return_cv_booster)

    if folds is None:
        idx = np.arange(n)
        if stratified and params.get("objective") in ("binary", "multiclass",
                                                      "multiclassova"):
            folds_idx = [[] for _ in range(nfold)]
            for cls in np.unique(y):
                cls_idx = idx[y == cls]
                if shuffle:
                    rng.shuffle(cls_idx)
                for i, part in enumerate(np.array_split(cls_idx, nfold)):
                    folds_idx[i].extend(part)
            folds = [(np.setdiff1d(idx, np.array(f)), np.array(sorted(f)))
                     for f in folds_idx]
        else:
            if shuffle:
                rng.shuffle(idx)
            parts = np.array_split(idx, nfold)
            folds = [(np.concatenate([p for j, p in enumerate(parts) if j != i]),
                      parts[i]) for i in range(nfold)]

    results: Dict[str, List[float]] = {}
    boosters = []
    fold_histories = []
    for tr_idx, va_idx in folds:
        dtr = Dataset(X[tr_idx], label=np.asarray(y)[tr_idx],
                      weight=None if train_set.weight is None
                      else train_set.weight[tr_idx],
                      params=params)
        dva = Dataset(X[va_idx], label=np.asarray(y)[va_idx],
                      weight=None if train_set.weight is None
                      else train_set.weight[va_idx],
                      reference=dtr, params=params)
        history: Dict[str, Dict[str, List[float]]] = {}
        cbs = list(callbacks or []) + [callback_mod.record_evaluation(history)]
        bst = train(params, dtr, num_boost_round, valid_sets=[dva],
                    valid_names=["valid"], callbacks=cbs)
        boosters.append(bst)
        fold_histories.append(history.get("valid", {}))

    return _collect_cv(results, fold_histories, boosters, return_cv_booster)


def _collect_cv(results, fold_histories, boosters, return_cv_booster):
    metric_names = sorted({m for h in fold_histories for m in h})
    for m in metric_names:
        rounds = min(len(h[m]) for h in fold_histories if m in h)
        vals = np.array([h[m][:rounds] for h in fold_histories if m in h])
        results[f"valid {m}-mean"] = list(vals.mean(axis=0))
        results[f"valid {m}-stdv"] = list(vals.std(axis=0))
    if return_cv_booster:
        results["cvbooster"] = boosters
    return results
