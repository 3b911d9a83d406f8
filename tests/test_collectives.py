"""Explicit collective primitives vs local reductions on the 8-device CPU mesh
(reference pattern: exercising the Network layer over loopback,
tests/distributed/_test_distributed.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lightgbm_tpu.parallel.mesh import DATA_AXIS, make_mesh
from lightgbm_tpu.parallel import collectives as C

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


@pytest.fixture
def mesh():
    return make_mesh(8, 1)


def _sharded(mesh, arr, spec):
    return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, spec))


def test_histogram_reduce_scatter_matches_sum(mesh):
    rng = np.random.RandomState(0)
    K, F, B = 8, 16, 32
    partials = rng.randn(K, F, B, 3).astype(np.float32)
    # global layout: per-shard partial hists stacked on the leading axis
    stacked = _sharded(mesh, partials.reshape(K * F, B, 3), P(DATA_AXIS))
    out = C.histogram_reduce_scatter(stacked, mesh)
    expect = partials.sum(axis=0)                    # (F, B, 3)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)


def test_reduce_scatter_then_allgather_roundtrip(mesh):
    rng = np.random.RandomState(1)
    K, F, B = 8, 8, 16
    partials = rng.randn(K, F, B, 3).astype(np.float32)
    stacked = _sharded(mesh, partials.reshape(K * F, B, 3), P(DATA_AXIS))
    owned = C.histogram_reduce_scatter(stacked, mesh)
    full = C.allgather_histogram(owned, mesh)
    np.testing.assert_allclose(np.asarray(full), partials.sum(axis=0),
                               rtol=1e-5, atol=1e-5)


def test_sync_global_best_split(mesh):
    gains = np.array([0.1, 3.0, 0.5, 2.0, 0.0, 1.0, 0.2, 0.9], np.float32)
    payload = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
    g, p = C.sync_global_best_split(
        _sharded(mesh, gains, P(DATA_AXIS)),
        _sharded(mesh, payload, P(DATA_AXIS, None)), mesh)
    assert float(g) == 3.0
    np.testing.assert_array_equal(np.asarray(p), payload[1])


def test_scalar_syncs(mesh):
    v = np.arange(8, dtype=np.float32)
    sh = _sharded(mesh, v, P(DATA_AXIS))
    assert float(C.global_sum(sh, mesh)[0]) == v.sum()
    assert float(C.global_min(sh, mesh)[0]) == 0.0
    assert float(C.global_max(sh, mesh)[0]) == 7.0


def test_global_mean_weighted(mesh):
    v = np.arange(8, dtype=np.float32)
    w = np.array([1, 1, 1, 1, 2, 2, 2, 2], np.float32)
    out = C.global_mean(_sharded(mesh, v, P(DATA_AXIS)),
                        _sharded(mesh, w, P(DATA_AXIS)), mesh)
    np.testing.assert_allclose(float(out[0]), (v * w).sum() / w.sum(),
                               rtol=1e-6)


def test_global_feature_vote(mesh):
    F = 10
    rng = np.random.RandomState(2)
    gains = rng.rand(8, F).astype(np.float32) * 0.1
    # every shard agrees features 3 and 7 are the best
    gains[:, 3] += 10.0
    gains[:, 7] += 5.0
    mask = C.global_feature_vote(
        _sharded(mesh, gains, P(DATA_AXIS, None)), top_k=2, mesh=mesh)
    mask = np.asarray(mask)
    assert mask[3] and mask[7]
    assert mask.sum() <= 4  # top-2k winners


def test_parse_machine_list_and_rank(tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel import distributed as D

    cfg = Config({"machines": "127.0.0.1:12400,10.0.0.2:12400",
                  "num_machines": 2})
    machines = D.parse_machine_list(cfg)
    assert machines == ["127.0.0.1:12400", "10.0.0.2:12400"]
    assert D.derive_rank(machines, 12400) == 0

    mlist = tmp_path / "mlist.txt"
    mlist.write_text("127.0.0.1:12401\n10.0.0.9:12401\n")
    cfg2 = Config({"machine_list_filename": str(mlist), "num_machines": 2})
    assert D.parse_machine_list(cfg2) == ["127.0.0.1:12401", "10.0.0.9:12401"]


def test_init_distributed_single_process_noop():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel import distributed as D

    rank, world = D.init_distributed(Config({"num_machines": 1}))
    assert (rank, world) == (0, 1)


def test_comm_backend_reaches_grower_reduce_scatter(mesh):
    """The reduce-scatter facade is now LIVE in the grower hot loop: a
    backend registered through register_comm_backend with a traceable
    ``histogram_reduce_scatter_local`` hook must be what the compiled
    sharded grower calls for its per-wave histogram reduce — and, when the
    hook is semantically a reduce-scatter, training results must be
    unchanged (round-trip)."""
    import lightgbm_tpu.models.grower as G
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import TrainData
    from lightgbm_tpu.models.gbdt import _split_config

    n, f = 8 * 2304, 8
    rng = np.random.RandomState(3)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    cfg = Config({"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 5, "verbosity": -1})
    td = TrainData.build(X, y, cfg)
    meta = td.feature_meta_device()
    args = (jnp.asarray(td.binned.bins),
            jnp.asarray((0.5 - y).astype(np.float32)),
            jnp.full(n, 0.25, jnp.float32), jnp.ones(n, jnp.float32),
            jnp.ones(f, bool), meta["num_bins_per_feature"],
            meta["nan_bins"], meta["is_categorical"], meta["monotone"])
    gcfg = G.GrowerConfig(num_leaves=15, num_bins=td.binned.max_num_bins,
                          split=_split_config(cfg), leaf_batch=2,
                          hist_comm="reduce_scatter")
    grow = G.make_grower(gcfg, mesh=mesh, data_axis=DATA_AXIS)
    assert grow.plan.reduce == "scatter"
    tree_ref, rl_ref = grow(*args)

    calls = []

    class TraceableBackend:
        def histogram_reduce_scatter_local(self, h, axis, dim):
            calls.append((str(h.dtype), dim))        # trace-time record
            return jax.lax.psum_scatter(h, axis, scatter_dimension=dim,
                                        tiled=True)

    try:
        C.register_comm_backend(TraceableBackend())
        grow2 = G.make_grower(gcfg, mesh=mesh, data_axis=DATA_AXIS)
        tree_inj, rl_inj = grow2(*args)
    finally:
        C.register_comm_backend(None)
    # the hook intercepted the wave + root reduces, scattering the feature
    # axis of (G, B, 3) / (W, G, B, 3) histograms
    assert calls and {d for _, d in calls} == {0, 1}, calls
    np.testing.assert_array_equal(np.asarray(tree_ref.split_feature),
                                  np.asarray(tree_inj.split_feature))
    np.testing.assert_array_equal(np.asarray(tree_ref.leaf_value),
                                  np.asarray(tree_inj.leaf_value))
    np.testing.assert_array_equal(np.asarray(rl_ref), np.asarray(rl_inj))


def test_comm_backend_injection(mesh):
    """External comm injection seam (reference
    LGBM_NetworkInitWithFunctions, c_api.cpp:2773): a registered backend
    replaces the built-in XLA collectives in the facade."""
    import lightgbm_tpu.parallel.collectives as C

    calls = []

    class FakeBackend:
        def global_sum(self, value, mesh, axis):
            calls.append("sum")
            return jnp.asarray(42.0)

    v = jnp.ones(8)
    builtin = float(np.asarray(C.global_sum(v, mesh)))
    try:
        C.register_comm_backend(FakeBackend())
        injected = float(np.asarray(C.global_sum(v, mesh)))
        # unhooked functions keep the XLA path
        mx = float(np.asarray(C.global_max(jnp.arange(8.0), mesh)))
    finally:
        C.register_comm_backend(None)
    assert injected == 42.0 and calls == ["sum"]
    assert builtin == 8.0 and mx == 7.0
    assert float(np.asarray(C.global_sum(v, mesh))) == 8.0
