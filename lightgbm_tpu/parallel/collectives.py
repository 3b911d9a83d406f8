"""Explicit collective-communication primitives over a device mesh.

Reference counterpart: the L1 ``Network`` layer (``include/LightGBM/network.h:89``,
``src/network/network.cpp``) — ``Allreduce`` (``network.cpp:68``),
``ReduceScatter`` (recursive halving, ``network.cpp:232``), ``Allgather``
(Bruck, ``network.cpp:121``), typed scalar syncs (``network.h:168-275``) — and
their call sites in the parallel tree learners
(``data_parallel_tree_learner.cpp:284`` histogram ReduceScatter,
``parallel_tree_learner.h`` ``SyncUpGlobalBestSplit``,
``voting_parallel_tree_learner.cpp`` ``GlobalVoting``).

TPU re-design: collectives are XLA ops over ICI/DCN issued inside
``shard_map`` — ``psum_scatter`` replaces recursive-halving ReduceScatter,
``all_gather`` replaces Bruck, ``psum/pmin/pmax`` replace the typed scalar
syncs.  The topology construction (BruckMap/RecursiveHalvingMap) has no
equivalent: XLA's collective scheduler owns the routing.

These primitives are the seams the distributed tree learners use; they are
also directly testable against local reductions on a virtual CPU mesh
(the reference's localhost mock-cluster pattern).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS


# ------------------------------------------------------- comm injection seam
# Reference LGBM_NetworkInitWithFunctions (src/c_api.cpp:2773): external
# integrations (Spark/SynapseML-style) inject their own reduce/allgather.
# Here the XLA compiler owns routing, so the seam wraps the *facade*: when a
# backend is registered, the facade functions below delegate to it instead
# of the shard_map+psum implementations.
_comm_backend = None


def register_comm_backend(backend) -> None:
    """Install an object with optional ``global_sum/global_min/global_max/
    global_mean/histogram_reduce_scatter/histogram_reduce_scatter_local/
    allgather_histogram`` callables; ``None`` restores the built-in XLA
    collectives.  The ``*_local`` hook is called from inside compiled
    ``shard_map`` bodies (the grower hot loop) and must be traceable."""
    global _comm_backend
    _comm_backend = backend


def _injected(name):
    fn = getattr(_comm_backend, name, None) if _comm_backend is not None \
        else None
    return fn


def histogram_reduce_scatter_local(local_hist: jnp.ndarray, axis: str,
                                   scatter_dim: int = 0) -> jnp.ndarray:
    """Shard-level histogram reduce-scatter (call INSIDE ``shard_map``).

    This is the live implementation the distributed wave grower's hot loop
    calls every wave (``models/grower.py``, ``tpu_hist_comm=reduce_scatter``):
    per-shard partial histograms go in, the globally-summed block of this
    shard's owned ``scatter_dim`` slice comes out — the reference's
    ``Network::ReduceScatter(..., HistogramSumReducer)``
    (``data_parallel_tree_learner.cpp:284``) as one XLA collective.

    The feature axis (``scatter_dim``) must already be padded to a multiple
    of the shard count.  A backend registered via
    :func:`register_comm_backend` may override it with a
    ``histogram_reduce_scatter_local`` callable — it runs inside jit, so the
    override must be traceable (jax ops only, no host round-trips; host-level
    backends like the C-API network-function seam should override the
    whole-array facade below instead).
    """
    fn = _injected("histogram_reduce_scatter_local")
    if fn is not None:
        return fn(local_hist, axis, scatter_dim)
    return jax.lax.psum_scatter(local_hist, axis,
                                scatter_dimension=scatter_dim, tiled=True)


def histogram_reduce_scatter(local_hist: jnp.ndarray, mesh: Mesh,
                             axis: str = DATA_AXIS) -> jnp.ndarray:
    """Sum per-shard histograms and leave each shard owning a feature block.

    Reference: ``DataParallelTreeLearner::FindBestSplits`` —
    ``Network::ReduceScatter(input_buffer, reduce_scatter_size, ...,
    HistogramSumReducer)`` (``data_parallel_tree_learner.cpp:284``): every rank
    contributes full local histograms and receives the globally-summed
    histograms of its owned features.

    ``local_hist``: (F, B, C) with one copy per device along ``axis`` (i.e. a
    shard_map-local value or an array sharded (axis, ...) holding per-shard
    partials).  Returns (F/K, B, C) per shard, concatenated to (F, B, C) in
    the global view sharded along features.
    """
    fn = _injected("histogram_reduce_scatter")
    if fn is not None:
        return fn(local_hist, mesh, axis)
    nshards = mesh.shape[axis]
    f = local_hist.shape[0]
    if f % nshards != 0:
        pad = nshards - f % nshards
        local_hist = jnp.pad(local_hist, ((0, pad), (0, 0), (0, 0)))

    def body(h):
        # h: this shard's full-F local histogram -> (F/K, B, C) owned block.
        return histogram_reduce_scatter_local(h, axis, 0)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=P(axis),      # stacked per-shard partials
        out_specs=P(axis),
    )(local_hist)


def allgather_histogram(owned: jnp.ndarray, mesh: Mesh,
                        axis: str = DATA_AXIS) -> jnp.ndarray:
    """Inverse of the scatter: every shard receives all owned blocks
    (reference Bruck ``Network::Allgather``, ``network.cpp:121``)."""
    fn = _injected("allgather_histogram")
    if fn is not None:
        return fn(owned, mesh, axis)
    def body(h):
        return jax.lax.all_gather(h, axis, axis=0, tiled=True)

    return jax.shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(),
                         check_vma=False)(owned)


def sync_global_best_split(gains: jnp.ndarray, payload: jnp.ndarray,
                           mesh: Mesh, axis: str = DATA_AXIS
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Argmax-by-gain across shards, returning the winning payload everywhere.

    Reference: ``SyncUpGlobalBestSplit`` (``parallel_tree_learner.h``) —
    Allgather the serialized per-rank best ``SplitInfo`` and pick max gain.
    ``gains``: per-shard scalar (sharded along ``axis``); ``payload``: per-shard
    1-D serialized split record.
    """
    def body(g, p):
        all_g = jax.lax.all_gather(g, axis, tiled=True)           # (K,)
        all_p = jax.lax.all_gather(p, axis, axis=0, tiled=True)   # (K, R)
        win = jnp.argmax(all_g)
        return all_g[win], all_p[win]

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis, None)),
        out_specs=(P(), P()),
        check_vma=False,
    )(gains, payload)


def _scalar_sync(reduce_fn, value: jnp.ndarray, mesh: Mesh,
                 axis: str) -> jnp.ndarray:
    def body(v):
        return reduce_fn(v, axis)

    return jax.shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(),
                         check_vma=False)(value)


def global_sum(value: jnp.ndarray, mesh: Mesh,
               axis: str = DATA_AXIS) -> jnp.ndarray:
    """reference ``Network::GlobalSyncUpBySum`` (``network.h:239``)."""
    fn = _injected("global_sum")
    if fn is not None:
        return fn(value, mesh, axis)
    return _scalar_sync(jax.lax.psum, value, mesh, axis)


def global_min(value: jnp.ndarray, mesh: Mesh,
               axis: str = DATA_AXIS) -> jnp.ndarray:
    """reference ``Network::GlobalSyncUpByMin`` (``network.h:168``)."""
    fn = _injected("global_min")
    if fn is not None:
        return fn(value, mesh, axis)
    return _scalar_sync(jax.lax.pmin, value, mesh, axis)


def global_max(value: jnp.ndarray, mesh: Mesh,
               axis: str = DATA_AXIS) -> jnp.ndarray:
    """reference ``Network::GlobalSyncUpByMax`` (``network.h:203``)."""
    fn = _injected("global_max")
    if fn is not None:
        return fn(value, mesh, axis)
    return _scalar_sync(jax.lax.pmax, value, mesh, axis)


def global_mean(value: jnp.ndarray, weight: jnp.ndarray, mesh: Mesh,
                axis: str = DATA_AXIS) -> jnp.ndarray:
    """Weighted mean across shards (reference ``GlobalSyncUpByMean``,
    ``network.h:263`` — used by boost-from-average, ``gbdt.cpp:313``)."""
    fn = _injected("global_mean")
    if fn is not None:
        return fn(value, weight, mesh, axis)
    def body(v, w):
        return jax.lax.psum(v * w, axis) / jnp.maximum(
            jax.lax.psum(w, axis), 1e-35)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                         out_specs=P(), check_vma=False)(value, weight)


# ----------------------------------------------------------------- voting mode
def global_feature_vote(local_gains: jnp.ndarray, top_k: int, mesh: Mesh,
                        axis: str = DATA_AXIS) -> jnp.ndarray:
    """PV-Tree voting (reference ``VotingParallelTreeLearner::GlobalVoting``,
    ``voting_parallel_tree_learner.cpp:~150``): each shard proposes its local
    top-k features by split gain; votes are summed globally and the top-2k
    features win.  Only the winners' histograms then cross the network.

    Standalone shard_map primitive (unit-tested); the production voting
    learner embeds the same vote inside the sharded grower's wave loop —
    ``models/grower.py`` ``_vote_best_batch`` — where it composes with the
    per-wave histogram reduce.

    ``local_gains``: (K, F) per-shard best gain per feature (sharded along
    ``axis``).  Returns a replicated (F,) bool mask of the selected features.
    """
    f = local_gains.shape[-1]
    k = min(top_k, f)

    def body(gains):
        g = gains[0]                                    # this shard's (F,)
        _, top_idx = jax.lax.top_k(g, k)
        votes = jnp.zeros(f, jnp.int32).at[top_idx].add(1)
        votes = jax.lax.psum(votes, axis)               # global vote count
        # winners: top-2k features by votes (gain as tie-break)
        score = votes.astype(jnp.float32) * 1e6 + jax.lax.psum(g, axis)
        _, win = jax.lax.top_k(score, min(2 * k, f))
        return jnp.zeros(f, bool).at[win].set(True)[None]

    mask = jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis))(local_gains)
    # All shards compute identical masks; take the first replica.
    return mask[0]
