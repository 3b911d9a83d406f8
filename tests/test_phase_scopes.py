"""Phase scopes inside the compiled iteration (telemetry.PHASES).

``phase(name)`` is ``jax.named_scope``: HLO metadata only.  These tests
lower the fused iteration, the unfused wave grower and the data-parallel
grower at a tiny shape on the CPU and check that every name of ``PHASES``
shows up in the lowered module's debug locations on the path that reaches
it, that every histogram-kernel launch ends its scope path in
``cols<C>/rows<R>``, the columns and rows the launch is handed, and that
the scopes change no tree.  (That the compiled TPU binary is the same is
shown once, by hand, with the v5e compiler — PERF.md, Findings PR 25.)
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import PHASES

N, F = 6000, 6           # > _MIN_BUCKET rows: the permutation layouts run
PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 5, "tpu_leaf_batch": 4, "metric": "none",
          "tpu_histogram_impl": "pallas"}      # interpreted on the CPU
PATHS = {
    # the default program of higgs.train: ONE fused Pallas call per wave
    "fused": ("boost/gradients", "boost/score_update", "grow/setup",
              "grow/select", "grow/partition", "grow/wave_gather",
              "grow/wave_unpack", "grow/scan", "grow/update", "grow/finish"),
    # msltr.train's program: one ragged histogram launch a wave, XLA
    # subtract + scan
    "unfused": ("boost/gradients", "boost/score_update", "grow/setup",
                "grow/select", "grow/partition", "grow/hist",
                "grow/subtract", "grow/scan", "grow/update", "grow/finish"),
    # the data-parallel learner: every collective sits under grow/reduce
    "sharded": ("grow/reduce", "grow/hist", "grow/partition"),
}


def _data():
    rng = np.random.RandomState(0)
    X = rng.randn(N, F).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.2 * rng.randn(N) > 0)
    return X, y.astype(np.float32)


def _iteration(wave_kernel: str):
    """``(fn, args)``: the fused iteration of a tiny Booster."""
    X, y = _data()
    params = dict(PARAMS, tpu_wave_kernel=wave_kernel)
    ds = lgb.Dataset(X, label=y)
    ds.construct(params)
    g = lgb.Booster(params=params, train_set=ds)._gbdt
    assert g.fused_path_active
    assert g.wave_fused_active is (wave_kernel == "fused")
    mask, fmask, _ = g._iter_masks(None, None)
    return g._fused_core, (g.bins_dev, g.scores, mask, fmask,
                           g.cfg.learning_rate)


def _sharded_grow():
    """``(fn, args)``: the wave grower per shard of a 2-device mesh."""
    import lightgbm_tpu.models.grower as G
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import TrainData
    from lightgbm_tpu.models.gbdt import _split_config
    from lightgbm_tpu.parallel.mesh import DATA_AXIS, make_mesh

    X, y = _data()
    cfg = Config({"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 5, "verbosity": -1})
    td = TrainData.build(X.astype(np.float64), y.astype(np.float64), cfg)
    meta = td.feature_meta_device()
    args = (jnp.asarray(td.binned.bins), jnp.asarray(0.5 - y),
            jnp.full(N, 0.25, jnp.float32), jnp.ones(N, jnp.float32),
            jnp.ones(F, bool), meta["num_bins_per_feature"],
            meta["nan_bins"], meta["is_categorical"], meta["monotone"])
    gcfg = G.GrowerConfig(num_leaves=15, num_bins=td.binned.max_num_bins,
                          split=_split_config(cfg), leaf_batch=4)
    grow = G.make_grower(gcfg, mesh=make_mesh(2, 1), data_axis=DATA_AXIS)
    return grow.raw, args


def _program(path: str):
    if path == "sharded":
        return _sharded_grow()
    return _iteration(path)


@pytest.fixture(scope="module")
def lowered():
    """Debug-location text of each path's lowered module, made once."""
    cache = {}

    def get(path):
        if path not in cache:
            fn, args = _program(path)
            cache[path] = jax.jit(fn).lower(*args).as_text(debug_info=True)
        return cache[path]
    return get


@pytest.mark.parametrize("path, name", [(p, n) for p, names in PATHS.items()
                                        for n in names])
def test_phase_scope_in_lowered_debug_locations(lowered, path, name):
    assert re.search(r'[/"]' + re.escape(name) + r'[/"]', lowered(path)), \
        f"{name} is not in the debug locations of the {path} program"


def test_every_phase_is_reached_and_nothing_else_is_named():
    assert {n for names in PATHS.values() for n in names} == set(PHASES)
    assert all(re.fullmatch(r"[a-z]+/[a-z_]+", n) for n in PHASES)
    with pytest.raises(ValueError, match="PHASES"):
        telemetry.phase("grow/no_such_phase")


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _walk(jaxpr, prefix=""):
    """``(equation, scope path)``: a sub-jaxpr's name stacks are relative
    to the equation that holds it."""
    for eqn in jaxpr.eqns:
        full = f"{prefix}/{eqn.source_info.name_stack}".strip("/")
        yield eqn, full
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub, full)


@pytest.mark.parametrize("path", ["fused", "unfused"])
def test_kernel_launch_sites_end_in_the_rows_they_are_handed(path):
    fn, args = _program(path)
    launches = []
    for eqn, scope in _walk(jax.make_jaxpr(fn)(*args).jaxpr):
        kernel = eqn.params.get("name")
        if kernel not in ("histogram_flat",      # bins (R, F)
                          "histogram_ragged",    # packed wave rows (T, F)
                          "fused_wave_call"):    # packed wave rows (T, F)
            continue
        rows, cols = eqn.invars[0].aval.shape    # F fits one launch here
        launches.append(kernel)
        assert scope.split("/")[-2:] == [f"cols{cols}", f"rows{rows}"], \
            (kernel, scope)
        assert any(p in scope for p in PHASES), scope
    assert launches.count("histogram_flat") == 1    # the root pass
    assert ("fused_wave_call" in launches) is (path == "fused")
    assert ("histogram_ragged" in launches) is (path == "unfused")
    assert len(launches) > 2                     # one launch per ladder step


@pytest.mark.parametrize("features,expect", [(28, 1), (137, 1), (700, 3)])
def test_a_wide_histogram_says_the_columns_one_launch_holds(features, expect):
    """At 700 features one leaf's histogram is several kernel launches over
    the same rows (the layout's column tile: 3 x 234; MS-LTR's 137 columns
    are ONE launch since PR 28), all under ONE scope path:
    ``cols<C>`` is the columns ONE launch holds, so rows x cols summed over
    the launches covers the histogram's cells once, and ``rows<R>`` stays
    the last segment (``hist_rows_useful`` reads it there)."""
    from lightgbm_tpu.ops.histogram import histogram_from_vals
    rows = 512
    jaxpr = jax.make_jaxpr(lambda b, v: histogram_from_vals(
        b, v, num_bins=255, impl="pallas"))(
            jnp.zeros((rows, features), jnp.uint8),
            jnp.zeros((rows, 3), jnp.float32)).jaxpr
    (flat, scope), = [(e, sc) for e, sc in _walk(jaxpr)
                      if e.params.get("name") == "histogram_flat"]
    launches = sum(e.primitive.name == "pallas_call"
                   for sub in _sub_jaxprs(flat) for e, _ in _walk(sub))
    cols_seg, rows_seg = scope.split("/")[-2:]
    assert rows_seg == f"rows{rows}" and cols_seg.startswith("cols")
    cols = int(cols_seg[4:])
    assert launches == expect
    assert cols * (launches - 1) < features <= cols * launches
    assert cols * launches <= features + launches - 1     # balanced chunks


def test_fused_wave_gather_is_handed_the_rows_the_wave_has():
    """The ragged wave: nothing under ``grow/wave_gather`` is larger than
    the most a wave can hold — the smaller siblings of disjoint leaves, at
    most half the rows, each of the W slots rounded up to whole row blocks
    — and the launches are the steps of ONE total-row ladder ending there.
    (The old form gathered W x the wave's largest bucket: up to W * N.)"""
    import lightgbm_tpu.models.grower as G
    from lightgbm_tpu.ops.pallas_wave import wave_layout

    fn, args = _program("fused")
    w = PARAMS["tpu_leaf_batch"]
    blk = wave_layout(F, 256, "f32")["rows_block"]
    cap = (N // (2 * blk) + w) * blk
    # the pin can tell them apart: the old form's SMALLEST launch
    # (W x the smallest bucket) is more than the ragged form's largest
    assert cap < w * G._MIN_BUCKET
    handed, largest = [], 0
    for eqn, scope in _walk(jax.make_jaxpr(fn)(*args).jaxpr):
        if "grow/wave_gather" not in scope:
            continue
        if eqn.params.get("name") == "fused_wave_call":
            handed.append(eqn.invars[0].aval.shape[0])
            assert eqn.invars[0].aval.ndim == 2     # (T, F), never (W, S, F)
            continue        # its (W, 2, C_PAD, F * b_pad) output has no rows
        # everything else here is indexed by packed rows: no dimension of
        # it may pass the cap
        largest = max([largest] + [d for v in eqn.outvars
                                   for d in getattr(v.aval, "shape", ())])
    assert handed == G._wave_row_ladder(w * blk, cap, blk)
    assert handed[-1] == cap and all(t % blk == 0 for t in handed)
    assert largest == cap, (largest, cap)


def test_unfused_wave_is_one_ragged_launch_handed_the_rows_the_wave_has():
    """The mirror of the fused pin on ``msltr.train``'s program: every
    kernel launch under ``grow/hist`` is the ragged call — the root's
    per-leaf ``histogram_flat`` sits under ``grow/setup`` — its path ends
    ``cols<C>/rows<T>`` with ``T`` the steps of ONE total-row ladder in
    odd multiples of granules of ``_WAVE_GRANULE`` rows (the kernel's block where that is
    larger), and nothing under
    ``grow/hist`` is larger than the most a wave can hold: half the rows
    and each of the W slots rounded up to a whole granule.  (The per-leaf
    form gathered every slot at its power-of-two bucket, W x 2 048 rows at
    the least and up to N for ONE slot.)"""
    import lightgbm_tpu.models.grower as G
    from lightgbm_tpu.ops.pallas_histogram import kernel_layout

    fn, args = _program("unfused")
    w = PARAMS["tpu_leaf_batch"]
    blk = kernel_layout(F, 256, "f32", 16384)[0]
    gran = max(G._WAVE_GRANULE, blk)
    ladder = G._ragged_wave_totals(N // 2, w, gran)
    cap = ladder[-1]
    assert (N // (2 * gran) + w) * gran <= cap < w * G._MIN_BUCKET
    handed, largest = [], 0
    for eqn, scope in _walk(jax.make_jaxpr(fn)(*args).jaxpr):
        if "grow/hist" not in scope:
            continue
        kernel = eqn.params.get("name")
        assert kernel != "histogram_flat", scope
        if kernel == "histogram_ragged":
            rows, cols = eqn.invars[0].aval.shape
            assert scope.split("/")[-2:] == [f"cols{cols}", f"rows{rows}"]
            assert eqn.outvars[0].aval.shape[:2] == (w, F)
            handed.append(rows)
            continue        # inside it: (W, C_PAD, F * b_pad) has no rows
        largest = max([largest] + [d for v in eqn.outvars
                                   for d in getattr(v.aval, "shape", ())])
    assert handed == ladder and all(t % (2 * gran) == gran for t in handed)
    assert largest == cap, (largest, cap)


@pytest.mark.parametrize("path", ["fused", "unfused"])
def test_wave_gauges_say_the_granule_and_the_ladder(path):
    """``hist.wave_granule`` / ``hist.wave_ladder_steps``, set while the
    grower is traced: the fused wave packs in kernel blocks, the unfused
    in ``_WAVE_GRANULE`` rows or its kernel's block, the larger."""
    import lightgbm_tpu.models.grower as G
    from lightgbm_tpu.ops.pallas_histogram import kernel_layout
    from lightgbm_tpu.telemetry.registry import registry

    w = PARAMS["tpu_leaf_batch"]
    gran = kernel_layout(F, 256, "f32", 16384)[0]
    ladder = G._wave_row_ladder(w * gran, (N // (2 * gran) + w) * gran, gran)
    if path == "unfused":
        gran = max(G._WAVE_GRANULE, gran)
        ladder = G._ragged_wave_totals(N // 2, w, gran)
    fn, args = _program(path)
    jax.make_jaxpr(fn)(*args)
    assert registry().gauge("hist.wave_granule").value == gran
    assert registry().gauge("hist.wave_ladder_steps").value == len(ladder)


@pytest.mark.parametrize("path", ["fused", "unfused", "sharded"])
def test_partition_pass_sits_under_grow_partition_with_its_rows(path):
    """The ONE partition pass: every step of its total-row ladder is a
    branch whose scope path ends in ``cols1/rows<R>`` — one column read a
    row, ``R`` rows handed — under ``grow/partition``, and holds the
    pass's three operations; the write into ``perm`` happens nowhere
    else.  (``benchmark/scopes.rows_fed`` sums ``rows<R>`` over KERNEL
    events only, so the histogram readings do not see these.)"""
    import lightgbm_tpu.models.grower as G

    fn, args = _program(path)
    n = N // 2 if path == "sharded" else N      # rows a shard
    w = PARAMS["tpu_leaf_batch"]
    blk = G._partition_block(n)
    ladder = G._wave_row_ladder(blk, (n // blk + w) * blk, blk)
    held = {}
    for eqn, scope in _walk(jax.make_jaxpr(fn)(*args).jaxpr):
        prim = eqn.primitive.name
        m = re.search(r"grow/partition/(?:.*/)?cols1/rows(\d+)(?:/|$)", scope)
        if m:
            held.setdefault(int(m.group(1)), set()).add(prim)
        if prim == "scatter" and eqn.outvars[0].aval.shape == (2 * n,):
            assert m, scope                     # perm is written here alone
        if prim == "dynamic_update_slice":
            assert "grow/partition" not in scope, scope
    assert sorted(held) == ladder, (sorted(held), ladder)
    for prims in held.values():
        assert {"gather", "dot_general", "scatter"} <= prims, prims


class _NoScope(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("wave_kernel", ["fused", "unfused"])
def test_trees_are_bitwise_what_they_are_without_scopes(monkeypatch,
                                                        wave_kernel):
    X, y = _data()
    params = dict(PARAMS, tpu_wave_kernel=wave_kernel)

    def model():
        return lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=3).model_to_string()

    scoped = model()
    monkeypatch.setattr(jax, "named_scope", lambda name: _NoScope())
    assert model() == scoped


def test_the_second_span_system_is_gone():
    import lightgbm_tpu.utils as utils
    import lightgbm_tpu.utils.timer as timer
    for gone in ("FunctionTimer", "global_timer"):
        assert not hasattr(utils, gone) and not hasattr(timer, gone)
    assert utils.Timer is timer.Timer        # telemetry.spans keeps it
