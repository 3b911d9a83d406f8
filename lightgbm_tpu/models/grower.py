"""Device-resident leaf-wise tree growth.

Reference counterparts: ``SerialTreeLearner::Train`` (``src/treelearner/
serial_tree_learner.cpp:179`` — pick best leaf, build smaller-sibling histogram,
subtract for the other, find best thresholds, partition rows) and the CUDA
device-resident learner (``cuda_single_gpu_tree_learner.cpp:158`` — per-leaf kernel
sequence with only scalars returning to host).

TPU re-design: the whole per-tree growth loop is ONE compiled XLA program —
a ``lax.while_loop`` with static trip bound ``num_leaves - 1`` over static-shape
state.  ONE permutation-layout body, ``_grow_wave`` (a wave of one is the
sequential leaf-wise case), in three layouts, and the mask body beside it;
which of them a configuration runs is ``capabilities.plan_growth``'s to say:

- **Permutation layout** (default, single device): a row-index permutation kept
  grouped by leaf (the reference's ``DataPartition``/``CUDADataPartition``), so
  every per-split op touches ONLY the splitting leaves' rows: the partition
  is ONE ragged pass per wave over the W segments packed back to back
  (``_partition_wave``), the unfused histogram gather a ``dynamic_slice``
  with a static power-of-two bucket chosen by a ``lax.switch`` on the leaf's
  row count.  Per-tree work is O(N · avg_depth) like the reference, not
  O(N · num_leaves).
- **Sharded permutation layout** (data-axis meshes): the SAME permutation
  machinery runs per-shard inside ``shard_map`` — each shard keeps a local
  row permutation grouped by leaf and histograms only its local slice of the
  splitting leaf.  ONE cross-shard histogram reduction runs per wave
  (the reference's histogram reduce, ``data_parallel_tree_learner.cpp:284``);
  its shape is governed by ``hist_comm``:

  * ``reduce_scatter`` (the ``auto`` default): a feature-sliced
    ``psum_scatter`` leaves each shard the reduced histograms of only its
    owned ``ceil(G/shards)`` feature block (the reference's
    ``Network::ReduceScatter`` + per-rank feature ownership), the split
    scan runs on just that slice, and the global winner is broadcast as
    one tiny SplitInfo payload per child (``SyncUpGlobalBestSplit``) —
    ~2x less comm and ``shards``-x less scan FLOPs/leaf-histogram memory
    than the replicated alternative.
  * ``allreduce``: a full ``psum`` replicates the global histograms on
    every shard and the split scan runs replicated.

  Either way every split decision is replicated across shards and per-tree
  cost stays O(N·depth / shards).
- **Feature-sharded permutation layout** (feature-only meshes, a wave of
  one): rows replicated, feature columns sharded; each shard histograms
  and scans its own columns, the winner syncs as one SplitInfo payload and
  the owner of the split column broadcasts the go-left vector
  (``_grow_fp``).
- **Mask layout** (hybrid meshes, compositions the feature-sharded layout
  refuses, tiny data): rows carry a
  ``row_leaf`` assignment vector and leaf membership is a predicate folded
  into the histogram contraction.  Slower (full-N pass per split) but works
  under arbitrary GSPMD shardings: reductions cross the mesh via
  compiler-inserted collectives (``data_parallel_tree_learner.cpp:284,441``).

Histograms are carried RAW in ``leaf_hist`` (int32 under quantized training)
and scaled to f32 only at split-scan consumption, so sibling subtraction is
EXACT integer arithmetic and cross-shard reduction moves integer tensors —
the reference's integer histogram reducers (``bin.h:48-81``).

``histogram_pool_size`` bounds the ``leaf_hist`` carry (reference
``HistogramPool``, ``serial_tree_learner.h``): instead of one resident
histogram per leaf (~523 MB f32 at the Yahoo-LTR shape (255, 700, 256, 3),
~1.5 GB at Epsilon F=2000) the perm/wave/sharded layouts carry a P-slot
pool with an int32 ``leaf->slot`` indirection — a slot is claimed when a
leaf's smaller-sibling histogram is built, the larger sibling's
subtraction lands in the parent's slot, eviction is LRU over unpinned
slots, and a miss (an evicted histogram needed again: splitting an old
leaf, forced splits) recomputes from the leaf's contiguous perm segment in
creation-time row order and re-reduces across shards like the resident
path.  Under ``hist_comm=reduce_scatter`` a slot holds only the owned
``ceil(G/K)`` feature slice, so the savings multiply.  The compositions
that keep full residency: ``capabilities.plan_growth`` (``why["pool"]``).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.histogram import histogram_from_vals, unpack_bins4
from ..ops.split import (BestSplit, SplitConfig, _prefix_sum, _resolve_tile,
                         best_split, leaf_gain, leaf_output, smoothed_output,
                         sync_best_split)
from ..telemetry.registry import registry
from ..telemetry.spans import kernel_rows, phase, segment
from .capabilities import PERM_MIN_ROWS as _MIN_BUCKET
from .capabilities import plan_growth

_NEG_INF = -jnp.inf


@dataclasses.dataclass(frozen=True)
class GrowerConfig:
    num_leaves: int = 31
    max_depth: int = -1
    num_bins: int = 256          # padded bin axis B
    split: SplitConfig = dataclasses.field(default_factory=SplitConfig)
    histogram_impl: str = "auto"
    rows_block: int = 16384
    # Per-node feature subsampling (reference ColSampler
    # feature_fraction_bynode); per-tree fraction is handled by the caller's
    # feature_mask.
    feature_fraction_bynode: float = 1.0
    # Interaction constraints (reference ColSampler::GetByNode,
    # col_sampler.hpp:92-111): tuple of tuples of feature ids.  A node may
    # split only on features on its branch plus any group CONTAINING the
    # whole branch feature set.
    interaction_groups: Optional[Tuple[Tuple[int, ...], ...]] = None
    # Permutation layout on/off (see module docstring).  Disabled under a
    # device mesh: dynamic_slice over globally-grouped rows would destroy the
    # row-sharding locality the distributed path relies on.
    gather_rows: bool = True
    # Wave growth: split up to this many leaves per while-loop step.  The
    # split SET stays best-first (each wave takes the current top-gain
    # leaves, truncated to the leaf budget by gain order); only the
    # interleaving differs from the reference's strictly sequential
    # leaf-wise order.  >1 packs the multi-sibling histogram kernel's M
    # dimension (siblings x channels, up to 128) and divides the
    # sequential-step count — the TPU-shaped analog of the CUDA learner's
    # per-leaf kernel pipeline (cuda_single_gpu_tree_learner.cpp:174).
    leaf_batch: int = 1
    # The row sampler beside this grower: "none", "goss_device" (the device
    # selection hands ``grow`` the in-bag row ids, ``sample_rows``),
    # "goss_host" or "bagging" (a row mask).  The growth plan turns it into
    # the form a sampled tree is grown in (``plan.sampling``).
    sampling: str = "none"
    # Quantized training (reference GradientDiscretizer,
    # gradient_discretizer.hpp:128): int8 grad/hess levels, int32 histogram
    # accumulation, per-iteration scales; see ops/quantize.py.
    quantized: bool = False
    num_grad_quant_bins: int = 4
    stochastic_rounding: bool = True
    quant_renew_leaf: bool = False
    # Voting-parallel (reference VotingParallelTreeLearner / PV-Tree,
    # voting_parallel_tree_learner.cpp): under a data mesh, keep leaf
    # histograms LOCAL; each shard votes its top-k features by local gain and
    # only the global top-2k features' histogram slices are psum'd — comm
    # volume drops from F*B to 2k*B per child.
    voting: bool = False
    vote_top_k: int = 20
    # EFB (reference FeatureGroup/FindGroups, feature_group.h:26): the bins
    # matrix holds G bundled columns; histograms/partitions run in bundle
    # space and per-ORIGINAL-feature views are reconstructed at split-scan
    # time (binning.FeatureBundles).  meta gains (feat_group, feat_offset).
    # ``hist_bins`` is the bundle-space bin axis (max_group_bins, can exceed
    # the scan axis ``num_bins``); 0 means equal to ``num_bins``.
    bundled: bool = False
    hist_bins: int = 0
    # Forced splits (reference ForceSplits, serial_tree_learner.cpp:620):
    # BFS-ordered tuples (feature, bin, left_child_idx, right_child_idx)
    # applied before gain-driven growth; indices refer into this tuple,
    # -1 = no forced child.
    forced_splits: Optional[Tuple[Tuple[int, int, int, int], ...]] = None
    # Intermediate monotone mode (reference IntermediateLeafConstraints,
    # monotone_constraints.hpp:516): per-leaf output bounds are recomputed
    # every step from the CURRENT outputs of leaves adjacent in feature
    # space (one vectorized O(L^2 F) rectangle-adjacency pass — the
    # TPU-shaped equivalent of the reference's recursive
    # GoUpToFindLeavesToUpdate tree walk), and every leaf's stored best
    # split is refreshed against the new bounds from its resident
    # histogram (the reference's RecomputeBestSplitForLeaf).  Composes
    # with wave growth through conflict-free wave selection: leaves
    # ORDERED by a monotone relation never split in the same wave, so the
    # pre-wave bounds stay valid through the wave and ONE refresh runs per
    # wave instead of per split.
    mono_intermediate: bool = False
    # Advanced monotone mode (reference AdvancedLeafConstraints,
    # monotone_constraints.hpp:583): on top of the intermediate per-step
    # refresh, the split scan sees PER-THRESHOLD child output bounds — a
    # neighbour's output only constrains the slice of the leaf's range that
    # is actually adjacent to it.  The reference realises this with
    # per-feature (threshold, constraint) slice lists plus cumulative
    # min/max arrays; the TPU shape is dense (L, F, B) bound tensors built
    # by vectorized scatter-min/max + cummin/cummax along the bin axis.
    mono_advanced: bool = False
    # Static per-feature monotone constraint vector (e.g. (-1, 0, 1, ...)),
    # required by mono_advanced to unroll its per-monotone-feature
    # constraint pass at trace time.
    mono_static: Optional[Tuple[int, ...]] = None
    # 4-bit bin packing (reference DenseBin IS_4BIT arm, dense_bin.hpp):
    # when every feature has <= 16 bins the (N, F) matrix is stored as
    # (N, ceil(F/2)) uint8 nibble pairs — the resident bin matrix and the
    # per-leaf row gathers halve, and the histogram kernels unpack in
    # VMEM/registers.  States the form the caller's bins are in; GBDT
    # sets it from its growth plan (no EFB bundling, no feature-parallel
    # layout).
    packed4: bool = False
    # Cross-shard histogram reduction for the data-parallel sharded-perm
    # paths (reference data_parallel_tree_learner.cpp:284).  "allreduce":
    # full-histogram psum + replicated split scan.  "reduce_scatter": a
    # feature-sliced psum_scatter leaves each shard only its owned
    # ceil(G/shards) feature block, the scan runs slice-local, and the
    # winner syncs via the one-hot SplitInfo payload broadcast
    # (SyncUpGlobalBestSplit) — ~2x less comm per wave, shards-x less
    # scan FLOPs.  "auto" = reduce_scatter whenever the composition
    # allows (the growth plan's ``reduce``); voting mode and the mask
    # layout keep their own reductions in every mode.
    hist_comm: str = "auto"
    # Bounded histogram pool (reference HistogramPool,
    # serial_tree_learner.h: LRU slots + recompute-on-miss), reference MB
    # semantics: the growth loop carries only P = floor(MB / slot_bytes)
    # leaf histograms (slot = one (G, B, 3) f32/int32 leaf histogram — the
    # owned ceil(G/K) slice under hist_comm=reduce_scatter, so the savings
    # multiply) behind an int32 leaf->slot indirection.  -1 = unbounded =
    # the full (L, G, B, 3) carry.  Auto-clamped to [2*leaf_batch + 1, L]
    # so the wave frontier (W parents pinned for sibling subtraction + W
    # freshly built smaller siblings) always fits.  Engages on the wave
    # body, single-device or data-sharded (the growth plan's ``pool``);
    # the mask layout, voting and the intermediate/advanced monotone
    # refresh keep full residency.
    histogram_pool_size: float = -1.0
    # Fused wave kernel (ops/pallas_wave.py): ONE pallas_call per wave
    # builds the smaller-sibling histograms, derives the larger siblings
    # by parent subtraction and runs the split scan without the (W, G, B,
    # 3) tensors leaving VMEM — vs one ragged histogram launch per wave
    # plus two more HBM passes (subtract + scan) unfused.  "auto" fuses only
    # where the capability checks pass AND the flat pallas kernel is the
    # live histogram impl (TPU backends); "fused" forces the kernel
    # (interpret-mode on CPU — how tier-1 exercises the kernel body);
    # "unfused" keeps build, subtract and scan apart.  The growth plan's
    # ``fused``.
    wave_kernel: str = "auto"
    # Training-health sentinel signals (resilience/health.py): True wires
    # the quantized int16-wire overflow guard's escalation into a
    # jax.debug.callback report instead of a silent int32 fallback.  False
    # (the default, tpu_health_policy=off) traces the EXACT pre-sentinel
    # program — no callbacks, no HLO change.
    health_signal: bool = False


class TreeArrays(NamedTuple):
    """Static-shape device tree (reference ``Tree``/``CUDATree``, ``tree.h:26``).

    ``left_child``/``right_child`` >= 0 index internal nodes; negative values are
    ``~leaf_index`` (the reference's encoding).
    """

    split_feature: jnp.ndarray   # (M,) i32
    split_bin: jnp.ndarray       # (M,) i32
    default_left: jnp.ndarray    # (M,) bool
    is_cat: jnp.ndarray          # (M,) bool
    cat_mask: jnp.ndarray        # (M, B) bool — bins routed LEFT
    left_child: jnp.ndarray      # (M,) i32
    right_child: jnp.ndarray     # (M,) i32
    split_gain: jnp.ndarray      # (M,) f32
    internal_value: jnp.ndarray  # (M,) f32
    internal_count: jnp.ndarray  # (M,) f32
    leaf_value: jnp.ndarray      # (L,) f32
    leaf_count: jnp.ndarray      # (L,) f32
    leaf_weight: jnp.ndarray     # (L,) f32 (sum of hessians)
    num_leaves: jnp.ndarray      # () i32

    @property
    def max_leaves(self) -> int:
        return self.leaf_value.shape[0]


def slice_tree_arrays(stacked: TreeArrays, j) -> TreeArrays:
    """Round-``j`` view of a ``(K, ...)``-stacked :class:`TreeArrays` — the
    shape the iteration-packed path's ``lax.scan`` emits (one stacked tree
    per boosting round; see ``GBDT.train_pack``)."""
    return jax.tree.map(lambda a: a[j], stacked)


class _GrowState(NamedTuple):
    num_leaves: jnp.ndarray      # () i32
    perm: jnp.ndarray            # (N + max_bucket,) i32 rows grouped by leaf
    leaf_start: jnp.ndarray      # (L,) i32 slice start per leaf
    leaf_rows: jnp.ndarray       # (L,) i32 physical row count per leaf
    leaf_hist: jnp.ndarray       # (P, G, B, 3) histogram POOL (P == L and
                                 #   slot == leaf id when unpooled; bounded
                                 #   P with leaf_slot indirection otherwise)
    leaf_slot: jnp.ndarray       # (L,) i32 pool slot per leaf, -1 evicted
                                 #   ((1,) dummy when unpooled)
    slot_leaf: jnp.ndarray       # (P,) i32 owner leaf per slot, -1 free
                                 #   ((1,) dummy when unpooled)
    slot_tick: jnp.ndarray       # (P,) i32 LRU stamp ((1,) dummy)
    tick: jnp.ndarray            # () i32 pool claim counter
    leaf_sum_grad: jnp.ndarray   # (L,)
    leaf_sum_hess: jnp.ndarray   # (L,)
    leaf_count: jnp.ndarray      # (L,) in-bag counts (histogram count channel)
    leaf_depth: jnp.ndarray      # (L,) i32
    leaf_parent: jnp.ndarray     # (L,) i32 node index (-1 root)
    leaf_is_left: jnp.ndarray    # (L,) bool
    best_gain: jnp.ndarray       # (L,) f32 (-inf inactive / unsplittable)
    best_feature: jnp.ndarray    # (L,) i32
    best_bin: jnp.ndarray        # (L,) i32
    best_default_left: jnp.ndarray  # (L,) bool
    best_is_cat: jnp.ndarray     # (L,) bool
    best_cat_mask: jnp.ndarray   # (L, B) bool
    best_gl: jnp.ndarray         # (L,) split child stats
    best_hl: jnp.ndarray
    best_cl: jnp.ndarray
    leaf_out: jnp.ndarray        # (L,) f32 leaf output (path-smoothed chain)
    leaf_lo: jnp.ndarray         # (L,) f32 monotone lower output bound
    leaf_hi: jnp.ndarray         # (L,) f32 monotone upper output bound
    feat_used: jnp.ndarray       # (F,) bool — features split on so far (CEGB)
    leaf_path: jnp.ndarray       # (L, F) bool — features on each leaf's path
    rng: jnp.ndarray             # (2,) u32 PRNG key (extra_trees / bynode)
    forced_leaf: jnp.ndarray     # (K,) i32 leaf of each pending forced split
    leaf_bin_lo: jnp.ndarray     # (L, F) i32 bin-rectangle bounds, or (1, 1)
    leaf_bin_hi: jnp.ndarray     #   dummies when mono_intermediate is off
    adv_llo: jnp.ndarray         # (L,) advanced mode: output bounds of each
    adv_lhi: jnp.ndarray         #   leaf's STORED best split's left/right
    adv_rlo: jnp.ndarray         #   children, gathered at (feature, bin)
    adv_rhi: jnp.ndarray         #   during refresh; (1,) dummies when off
    tree: TreeArrays


@phase("grow/reduce")
def _psum(x, axis):
    """Every cross-shard sum of the learners goes through here, so a device
    trace finds the collectives under one name (``grow/reduce``)."""
    return jax.lax.psum(x, axis)


@phase("grow/update")
def _store_best(state: _GrowState, leaf: jnp.ndarray, bs: BestSplit,
                depth_ok: jnp.ndarray) -> _GrowState:
    gain = jnp.where(depth_ok, bs.gain, _NEG_INF)
    return state._replace(
        best_gain=state.best_gain.at[leaf].set(gain),
        best_feature=state.best_feature.at[leaf].set(bs.feature),
        best_bin=state.best_bin.at[leaf].set(bs.bin),
        best_default_left=state.best_default_left.at[leaf].set(bs.default_left),
        best_is_cat=state.best_is_cat.at[leaf].set(bs.is_cat),
        best_cat_mask=state.best_cat_mask.at[leaf].set(bs.cat_mask),
        best_gl=state.best_gl.at[leaf].set(bs.sum_grad_left),
        best_hl=state.best_hl.at[leaf].set(bs.sum_hess_left),
        best_cl=state.best_cl.at[leaf].set(bs.count_left),
    )


def _split_buckets(n: int) -> list:
    """Static slice sizes covering leaf row counts 1..n."""
    sizes = []
    b = _MIN_BUCKET
    while b < n:
        sizes.append(b)
        b *= 2
    sizes.append(n)
    return sizes


# Steps per octave of the ragged fused wave's TOTAL-row ladder.  The kernel
# skips a step's padding blocks, so only the gather that feeds it pays for
# them: 2 steps cap that padding at 1.41x (1 step: the power-of-two 2x)
# for twice the compiled kernel instances.  On a v5e at 1.5 M x 28
# (PERF.md, Findings PR 26): 1 / 2 / 4 steps hand the kernels 74.8 / 85.9
# / 92.4 % needed rows, gather 0.199 / 0.157 / 0.151 s an iteration, and
# compile cold in 75 / 82 / 101 s.
_WAVE_LADDER_STEPS = 2


# Rows per granule of the UNFUSED ragged wave's packing (the kernel's row
# block where that is larger).  The packing's granule is not the kernel's
# block: one slice of ``perm`` costs 1.5-1.9 us whatever it holds, every
# slot pads its last granule in the gather, and the kernel skips the
# padding blocks inside a granule.  On a v5e (PERF.md, Findings PR 34: one
# wave of 16 slots, gather + launch, at 128 / 256 / 512 / 1024 / 2048 rows
# a granule): 2.27 M x 137, a wave of 693 K rows 38.5 / 33.7 / 32.0 / 34.6
# / 34.5 ms and one of 23 K rows 3.6 (256) / 3.9 / 4.0 / 4.3; 400 K x 2000,
# 100 K rows 45.9 (128) / 45.4 (512) / 49.2 (2048), 7 K rows 9.1 / 9.3 /
# 10.2.  512 is the best or within 6 % of it on each; the partition pass's
# block (2 048 at 2.27 M rows) is 7-12 % behind on its own shape.
_WAVE_GRANULE = 512


def _wave_row_ladder(lo: int, hi: int, blk: int) -> list:
    """Static TOTAL-row sizes of the ragged fused wave, whole row blocks
    each, ascending: ``hi`` (the most a wave can hold) and
    ``_WAVE_LADDER_STEPS`` geometric steps per octave below it, down to
    ``lo`` (never under the perm layouts' smallest bucket; a step within
    half a step of it is left out)."""
    lo = max(lo, -(-_MIN_BUCKET // blk) * blk)
    sizes, k = [], 0
    while True:
        t = -(-int(hi / 2.0 ** (k / _WAVE_LADDER_STEPS)) // blk) * blk
        if t <= lo * 2.0 ** (0.5 / _WAVE_LADDER_STEPS):
            return [max(lo, t)] + sizes[::-1]
        if not sizes or t < sizes[-1]:
            sizes.append(t)
        k += 1


def _ragged_wave_totals(most: int, w: int, gran: int) -> list:
    """Static TOTAL-row sizes of the unfused ragged wave
    (``_grow_wave._ragged_wave``): the ladder from ``w`` granules (every
    slot holds one) to ``most`` rows with each of the ``w`` slots rounded up
    to a whole granule.  Two shapes the chip asked for (PERF.md, Findings
    PR 34):

    - under a sixteenth of the top only every other step is kept.  An
      instance is a kernel and some 1 000 instructions to trace, compile
      and load in every process (15 instances at 2.27 M rows read 3.8 s of
      set-up over the per-leaf buckets' 12), and the waves down there are
      a few in a hundred of the rows a tree hands over;
    - every step is moved up to an ODD multiple of the granule.  XLA's row
      gather into ``(T, 137)`` bytes reads 11.5 ms at every ``T = odd x
      512`` tried around 697 K rows and 15.7-16.4 ms at every even multiple
      (a multiple of 1 024), 8.2 against 9.3 ms at 105 K x 2 000 — gather +
      launch 9 % and 2 % apart (the per-leaf buckets were powers of two)."""
    hi = (most // gran + w) * gran
    steps = _wave_row_ladder(w * gran, hi, gran)
    low = [t for t in steps if 16 * t < hi]
    steps = low[-2::-2][::-1] + steps[len(low):]
    return sorted({t + gran * (1 - (t // gran) % 2) for t in steps})


def _ladder_step(sizes, x):
    """Index of the first of the ascending static ``sizes`` that holds
    ``x`` (the last one if none does): the ``lax.switch`` index of a
    bucket or of a total-row ladder."""
    return jnp.clip(jnp.searchsorted(sizes, x, side="left"),
                    0, sizes.shape[0] - 1).astype(jnp.int32)


def _partition_block(n: int) -> int:
    """Rows per block of the partition pass's packing: a power of two near
    ``n / 1024`` within 256..2048.  The pass has no kernel tile to respect:
    slicing a block's rows out of ``perm`` costs the same 2-3 us whatever
    it holds, and every split leaf pads its last block — on a v5e 2 048
    and 8 192 rows read alike on a wave of most of 1.5 M rows and 8 192
    reads 1.6x worse on a wave of 45 k (PERF.md, Findings PR 30)."""
    return min(2048, max(256, 1 << (n // 1024).bit_length()))


def _offset_rows(inner):
    """``(T / 128, 128)`` inclusive prefix sums inside each row -> the
    ``(T,)`` inclusive prefix sum over all of them: a cumsum over the
    ``T / 128`` row totals offsets the rows."""
    total = inner[:, -1]
    return (inner + (jnp.cumsum(total) - total)[:, None]).reshape(-1)


def _prefix_count(bits):
    """Inclusive prefix count of a ``(T,)`` bool vector, ``T`` a multiple of
    128, through the MXU: rows of 128 times an upper-triangular matrix of
    ones count inside each row (0/1 is exact in bf16 and a row's count in
    the f32 accumulator), and ``_offset_rows`` offsets them.  On a v5e it
    runs like ``jnp.cumsum`` and compiles in a
    fraction of its ``reduce-window``'s time (PERF.md, Findings PR 30)."""
    rows = bits.reshape(-1, 128).astype(jnp.bfloat16)
    tri = (jnp.arange(128)[:, None] <= jnp.arange(128)[None, :]).astype(
        jnp.bfloat16)
    return _offset_rows(jnp.dot(
        rows, tri, preferred_element_type=jnp.float32).astype(jnp.int32))


def _partition_wave(perm, starts, cnts, go_left, n):
    """THE partition of the permutation layout: a stable two-way partition
    of the W disjoint ``perm`` segments ``[starts[j], starts[j] + cnts[j])``
    in ONE ragged pass — one read of the go-left bit per row, one prefix
    sum, one scatter.

    ``go_left`` holds the wave's W splits by row id, one bit a split
    (``_go_left_bits``): split ``j`` of row ``r`` is bit ``j % 32`` of word
    ``[(j // 32) * (n + 1) + r]``.  The segments are packed back to back in
    whole row blocks (``ops/pallas_wave.wave_block_slots``; an empty or
    inactive slot holds no block) and padded only to the next step of a
    TOTAL-row ladder (``_wave_row_ladder``), whose branch carries the rows
    it is handed in its scope path (``rows<R>``).  ONE inclusive prefix
    count of the bits over the packed rows, less its value at each slot's
    first row, is a row's rank among its slot's left rows; a right row's
    rank is its place in the slot less the left rows before it.  ONE
    scatter writes every real row straight to ``starts[slot] + rank`` (the
    destinations are a permutation of the segments: ``unique_indices``);
    the padding rows go out of range and are dropped, so every other
    position of ``perm`` is untouched.  Returns ``(perm, nl)``, ``nl[j]``
    the rows slot ``j`` sent left (0 for an empty slot)."""
    from ..ops.pallas_wave import wave_block_slots

    W = starts.shape[0]
    blk = _partition_block(n)
    totals = _wave_row_ladder(blk, (n // blk + W) * blk, blk)
    size = perm.shape[0]
    nb = (cnts.astype(jnp.int32) + (blk - 1)) // blk
    off = jnp.cumsum(nb) - nb
    ti = _ladder_step(jnp.asarray(totals, jnp.int32), jnp.sum(nb) * blk)

    def branch_for(T):
        def br(perm):
            with kernel_rows(T, 1):
                slot, k = wave_block_slots(off, T // blk)
                row0 = k * blk
                seg = jax.vmap(lambda s0: jax.lax.dynamic_slice(
                    perm, (s0,), (blk,)))(starts[slot] + row0)
                place = row0[:, None] + jnp.arange(blk, dtype=jnp.int32)
                valid = place < cnts[slot][:, None]             # (T/blk, blk)
                word = go_left.at[((slot >> 5) * (n + 1))[:, None] + seg].get(
                    mode="promise_in_bounds")
                gl = (((word >> (slot & 31)[:, None]) & 1) > 0) & valid
                incl = _prefix_count(gl.reshape(T))
                excl = jnp.concatenate([incl - gl.reshape(T), incl[-1:]])
                base = excl[jnp.minimum(off * blk, T)]          # (W,)
                nl = jnp.concatenate([base[1:], incl[-1:]]) - base
                lrank = excl[:T].reshape(gl.shape) - base[slot][:, None]
                rank = jnp.where(gl, lrank,
                                 nl[slot][:, None] + place - lrank)
                # padding rows: out of range, and still no two alike
                dest = jnp.where(
                    valid, starts[slot][:, None] + rank,
                    size + jnp.arange(T, dtype=jnp.int32).reshape(gl.shape))
                return perm.at[dest.reshape(T)].set(
                    seg.reshape(T), mode="drop", unique_indices=True), nl
        return br

    return jax.lax.switch(ti, [branch_for(T) for T in totals], perm)


def _row_leaf_map(leaf_start, leaf_rows, num_leaves, perm, n, sentinel):
    """Row id -> leaf id from the final grouped permutation: position ``i``
    of ``perm[:n]`` belongs to the live leaf whose ``[start, start + rows)``
    holds ``i``, and that leaf id is piecewise constant over at most L
    ranges.  So no position searches for its range: the CHANGE of leaf id
    is scatter-added at each live start in ascending order (L numbers into
    ``n`` zeros) and ONE inclusive int32 prefix sum over the positions
    turns the changes into ids — shift-and-add inside rows of 128
    (``ops/split._prefix_sum``), the rows offset by ``_offset_rows`` —
    exact for every ``num_leaves``, with no per-row table read and
    no loop (the per-position ``searchsorted`` it replaces read 62-66 ns a
    row on a v5e, this 0.03; PERF.md, Findings PR 32).  A position before
    the first start reads the first leaf and a gap the range before it, as
    ``clip(searchsorted(..) - 1, 0, L - 1)`` did.  One scatter by
    ``perm[:n]`` then puts the ids in row order.

    Zero-row leaves (possible per shard under the sharded layout) share
    their start with a sibling, and the slots past ``num_leaves`` hold
    whatever the last tree left: both take ``sentinel``, a position no row
    has, sort last and fall off the end (``mode="drop"``; one that lands in
    the padding of the last row of 128 is sliced away)."""
    L = leaf_start.shape[0]
    starts = jnp.where((jnp.arange(L) < num_leaves) & (leaf_rows > 0),
                       leaf_start, sentinel)
    order = jnp.argsort(starts).astype(jnp.int32)
    at = starts[order].at[0].set(0)     # before the first start: its leaf
    change = jnp.zeros(-(-n // 128) * 128, jnp.int32).at[at].add(
        jnp.diff(order, prepend=0), mode="drop")
    pos_leaf = _offset_rows(_prefix_sum(change.reshape(-1, 128)))[:n]
    return jnp.zeros(n, jnp.int32).at[perm[:n]].set(pos_leaf)


def _route_rows(row_leaf, go_left, leaf_j, newleaf_j):
    """One wave of the dense row -> leaf update, over EVERY row
    (``row_leaf`` is as long as the bit table's rows, ``n + 1`` with the
    phantom row, so the table is read in place): a row of leaf
    ``leaf_j[j]`` whose go-left bit ``j`` (``_go_left_bits``' table over
    all rows, by row id) is clear moves to ``newleaf_j[j]``; every other
    row stays.  W compares and selects a row in one elementwise pass, no
    gather — so the rows a sampled tree was not grown on reach their leaf
    at the price of W dense column reads a wave (0.018 s/iter at 2.27 M
    rows on a v5e; a per-row walk of the finished tree costs a gather a
    level, 0.4-0.9 s/iter there: PERF.md, Findings PR 33)."""
    words = go_left.reshape(-1, row_leaf.shape[0])
    new = row_leaf
    for j in range(leaf_j.shape[0]):
        right = ((words[j >> 5] >> (j & 31)) & 1) == 0
        new = jnp.where((row_leaf == leaf_j[j]) & right, newleaf_j[j], new)
    return new


# The operand size from which ``_take_rows`` slices (see ``_sliced``).
_SLICE_MIN_BYTES = 1 << 29


def _sliced(a, w: int) -> bool:
    """Whether ``_take_rows`` reads ``w`` rows of ``a`` as slices.  XLA's
    TPU gather of whole rows out of a LARGE operand slices every column
    chunk of it out first and then loops over the rows — a pass over all
    of it, read and written: 4.5 ms a wave on a v5e for the (255, 2000,
    255, 3) float32 histogram carry, 2.1 ms for the ``(2000, N + 1)``
    feature-major bins at 400 000 rows — where ``w`` slices move the rows
    alone.  Under half a GiB (every operand of the 28- and 137-column
    cells, 22-311 MB) that pass is short or the gather native, and the
    slices cost more than they save: 3.7 % of the iteration with
    ``higgs``'s 22 MB carry sliced (PERF.md, Findings).  And a slice of a
    2-D operand reads the whole tile its row lies in (8 rows of 32-bit
    words: 32 rows of bytes), so ``w`` of them must read fewer bytes than
    the pass."""
    tile = 32 // a.dtype.itemsize if a.ndim == 2 else 1
    return (a.size * a.dtype.itemsize >= _SLICE_MIN_BYTES
            and w * (min(tile, a.shape[0]) + 1) < 2 * a.shape[0])


def _take_rows(a, idx):
    """``a[idx]`` for a short static-length index vector: one
    ``dynamic_slice`` a row, stacked, where that moves fewer bytes than a
    gather (``_sliced``).  Out-of-range indices clamp, as the gather's
    do."""
    if not _sliced(a, idx.shape[0]):
        return a[idx]
    return jnp.stack([jax.lax.dynamic_index_in_dim(a, idx[j], keepdims=False)
                      for j in range(idx.shape[0])])


def _take_rows_bytes(a, w: int) -> int:
    """The bytes ``_take_rows`` moves as slices for ``w`` rows of ``a``:
    the rows, or 0 where it gathers."""
    if not _sliced(a, w):
        return 0
    return w * (a.size // a.shape[0]) * a.dtype.itemsize


def _pack_bits(bits):
    """``(W, M)`` bools -> ``(ceil(W / 32) * M,)`` int32: row ``j`` is bit
    ``j % 32`` of the words ``[(j // 32) * M : (j // 32 + 1) * M]``."""
    w, m = bits.shape
    pad = jnp.pad(bits, ((0, -w % 32), (0, 0))).reshape(-1, 32, m)
    return jnp.sum(pad.astype(jnp.int32)
                   << jnp.arange(32, dtype=jnp.int32)[None, :, None],
                   axis=1).reshape(-1)


def _decode_col(cfg, raw, feat, meta):
    """Bundle-space bin -> original-feature bin for row partitioning."""
    if not cfg.bundled:
        return raw
    nbpf, fo = meta[0], meta[5]
    off = fo[feat]
    nb = nbpf[feat]
    return jnp.where(
        off < 0, raw,
        jnp.where((raw >= off) & (raw < off + nb - 1), raw - off + 1, 0))


def _go_left_bits(cfg, bins_fm, meta, feats, sbins, dlefts, scats, cmasks):
    """The wave's W splits applied to EVERY row, by row id: the ``go_left``
    table of ``_partition_wave``.  Dense and sequential — split ``j`` reads
    its column as ONE row of the feature-major bins ``bins_fm`` (``(G, N +
    1)``, made once a tree), W x N bytes a wave — so that the pass's one
    random read a row is a 1-D gather of a 32-bit word, the cheapest
    gather a v5e has (7 ns a row against 13-22 for one byte out of the
    ``(N + 1, G)`` matrix; PERF.md, Findings PR 30).  Under EFB the column
    is decoded from its bundle, under packed4 from its nibble; a
    categorical split looks its bin up in the mask's 32-bit words."""
    col = lambda a: a[:, None]
    gcols = meta[4][feats] if cfg.bundled else feats
    raw = _take_rows(bins_fm, gcols // 2 if cfg.packed4 else gcols
                     ).astype(jnp.int32)
    if cfg.packed4:
        raw = jnp.where(col(gcols) % 2 == 0, raw & 15, (raw >> 4) & 15)
    bins = _decode_col(cfg, raw, col(feats), meta)              # (W, N + 1)
    go_left = bins <= col(sbins)
    if cfg.split.has_categorical:
        words = _pack_bits(cmasks.T).reshape(-1, cmasks.shape[0])
        word = jnp.zeros_like(bins)
        for k in range(words.shape[0]):
            word = jnp.where((bins >> 5) == k, col(words[k]), word)
        go_left = jnp.where(col(scats), ((word >> (bins & 31)) & 1) > 0,
                            go_left)
    go_left = jnp.where((bins == col(meta[1][feats])) & ~col(scats),
                        col(dlefts), go_left)
    return _pack_bits(go_left)


def make_grower(cfg: GrowerConfig, mesh=None, data_axis: str = "data"):
    """Build the jitted ``grow(bins, grad, hess, sample_mask, feature_mask, meta...)``
    function.  All shapes/hyper-params are compile-time; data is traced.

    With ``mesh`` (and ``cfg.gather_rows``), the permutation/wave layouts run
    per-shard inside ``shard_map`` over ``data_axis`` with one histogram
    reduction per wave — a feature-sliced ``psum_scatter`` or a full
    ``psum``, per ``cfg.hist_comm`` (see module docstring).

    Which body, layout, kernel and reduction run is read off the growth
    plan (``capabilities.plan_growth``): its static half here, completed
    in ``_grow_impl`` for the shapes the program is traced at."""

    L, B = cfg.num_leaves, cfg.num_bins
    static = plan_growth(cfg, mesh, data_axis, rows=None, features=None)
    HB = cfg.hist_bins or cfg.num_bins   # histogram-storage bin axis
    forced = cfg.forced_splits or ()
    n_forced = min(len(forced), max(L - 1, 0))
    if n_forced:
        _fs = np.asarray(forced[:n_forced], np.int32)
        F_FEAT = jnp.asarray(_fs[:, 0])
        F_BIN = jnp.asarray(_fs[:, 1])
        F_LC = jnp.asarray(_fs[:, 2])
        F_RC = jnp.asarray(_fs[:, 3])
    M = max(L - 1, 1)
    use_rand = cfg.split.extra_trees
    use_bynode = cfg.feature_fraction_bynode < 1.0
    need_key = use_rand or use_bynode
    use_groups = bool(cfg.interaction_groups)
    track_path = cfg.split.use_cegb or use_groups

    def _groups_matrix(f):
        gm = np.zeros((len(cfg.interaction_groups), f), bool)
        for gi, grp in enumerate(cfg.interaction_groups):
            for feat in grp:
                if 0 <= feat < f:
                    gm[gi, feat] = True
        return jnp.asarray(gm)

    def _allowed_for_paths(pathk, groups_mat):
        """(k, F) allowed-feature masks per branch (reference
        ColSampler::GetByNode): branch features plus every group containing
        the whole branch set; an empty branch allows all groups' union."""
        ok = ~jnp.any(pathk[:, None, :] & ~groups_mat[None, :, :], axis=2)
        allowed = jnp.any(ok[:, :, None] & groups_mat[None, :, :], axis=1)
        return pathk | allowed

    def _node_inputs(key, feature_mask, nbpf):
        """Per-node (fmask, rand_bins): extra_trees draws ONE random
        threshold per feature; feature_fraction_bynode re-samples the
        feature set per node (reference ColSampler ResetByNode)."""
        rand_bins = None
        fmask = feature_mask
        if use_rand:
            key, k1 = jax.random.split(key)
            draw = jax.random.randint(k1, nbpf.shape, 0, 1 << 30)
            rand_bins = draw % jnp.maximum(nbpf, 1)
        if use_bynode:
            key, k2 = jax.random.split(key)
            sel = jax.random.uniform(k2, fmask.shape) \
                < cfg.feature_fraction_bynode
            # keep at least one usable feature (reference ColSampler)
            fmask = jnp.where(jnp.any(sel & fmask), fmask & sel, fmask)
        return fmask, rand_bins

    @phase("grow/scan")
    def _best_for(hist, pg, ph, pc, meta, feature_mask, penalty=None,
                  parent_out=None, key=None, path=None, groups_mat=None,
                  out_lo=None, out_hi=None, leaf_depth=None, rs=None):
        nbpf, nan_bins, is_cat, monotone = meta[:4]
        rand_bins = None
        if need_key and key is not None:
            feature_mask, rand_bins = _node_inputs(key, feature_mask, nbpf)
        if use_groups and path is not None and groups_mat is not None:
            feature_mask = feature_mask & _allowed_for_paths(
                path[None, :], groups_mat)[0]
        if rs is not None:
            # Slice-local scan: per-node inputs were derived replicated in
            # full feature space (identical draws on every shard); project
            # them onto this shard's owned window.
            feature_mask, rand_bins, penalty = rs["project"](
                feature_mask, rand_bins, penalty)
            nbpf, nan_bins, is_cat, monotone = rs["meta_s"]
        return best_split(
            hist, pg, ph, pc,
            num_bins_per_feature=nbpf, nan_bins=nan_bins, is_categorical=is_cat,
            monotone=monotone, feature_mask=feature_mask, cfg=cfg.split,
            gain_penalty=penalty, parent_output=parent_out,
            rand_bins=rand_bins, out_lo=out_lo, out_hi=out_hi,
            leaf_depth=leaf_depth,
        )

    def _batch_node_inputs(key, feature_mask, nbpf, k):
        """Per-node (fmask (k,F), rand_bins (k,F) or None) for k children."""
        fmaskk = jnp.broadcast_to(feature_mask, (k,) + feature_mask.shape)
        randk = None
        if not need_key or key is None:
            return fmaskk, randk
        if use_rand:
            key, k1 = jax.random.split(key)
            draw = jax.random.randint(k1, (k,) + nbpf.shape, 0, 1 << 30)
            randk = draw % jnp.maximum(nbpf, 1)[None, :]
        if use_bynode:
            key, k2 = jax.random.split(key)
            sel = jax.random.uniform(k2, fmaskk.shape) \
                < cfg.feature_fraction_bynode
            keep = jnp.any(sel & fmaskk, axis=1, keepdims=True)
            fmaskk = jnp.where(keep, fmaskk & sel, fmaskk)
        return fmaskk, randk

    def _node_scan_inputs(key, feature_mask, nbpf, k, pathk, groups_mat):
        """Per-node (fmask, rand_bins) incl. the interaction-constraint
        path mask — ONE derivation shared by the data-parallel and voting
        scans so their per-node option semantics cannot diverge."""
        fmaskk, randk = _batch_node_inputs(key, feature_mask, nbpf, k)
        if use_groups and pathk is not None and groups_mat is not None:
            fmaskk = fmaskk & _allowed_for_paths(pathk, groups_mat)
        return fmaskk, randk

    @phase("grow/scan")
    def _best_for_batch(histk, pgk, phk, pck, meta, feature_mask,
                        penaltyk=None, parent_outk=None, key=None,
                        pathk=None, groups_mat=None, boundsk=None,
                        depthk=None, advk=None, rs=None):
        """All k children's split searches in one vmapped program — one
        kernel set per wave instead of per child."""
        nbpf, nan_bins, is_cat, monotone = meta[:4]
        k = histk.shape[0]
        if parent_outk is None:
            parent_outk = jnp.zeros(k, jnp.float32)
        fmaskk, randk = _node_scan_inputs(key, feature_mask, nbpf, k,
                                          pathk, groups_mat)
        if rs is not None:
            # Slice-local scan (see _best_for): node inputs derive
            # replicated, then project onto the owned feature window.  The
            # advanced-monotone bound tensors never reach this path
            # (the plan never scatters under the refresh modes).
            assert advk is None
            fmaskk, randk, penaltyk = rs["project"](fmaskk, randk, penaltyk)
            nbpf, nan_bins, is_cat, monotone = rs["meta_s"]
        if boundsk is None:
            lok = hik = jnp.zeros(k, jnp.float32)
            use_b = False
        else:
            lok, hik = boundsk
            use_b = True
        if depthk is None:
            depthk = jnp.zeros(k, jnp.int32)

        def one(hist, pg, ph, pc, penalty, pout, fmask, rand_bins, lo, hi,
                dep, adv=None):
            return best_split(
                hist, pg, ph, pc,
                num_bins_per_feature=nbpf, nan_bins=nan_bins,
                is_categorical=is_cat, monotone=monotone,
                feature_mask=fmask, cfg=cfg.split,
                gain_penalty=penalty, parent_output=pout,
                rand_bins=rand_bins,
                out_lo=lo if use_b else None,
                out_hi=hi if use_b else None,
                adv_bounds=adv,
                leaf_depth=dep,
            )

        if advk is not None:
            # Advanced monotone refresh: per-leaf (F, B) child-bound slices
            # ride along the vmap.  randk is statically None on this path
            # (extra_trees / bynode are rejected by the inter/adv checks).
            if penaltyk is None:
                return jax.vmap(
                    lambda h, g, hh, c, po, fm, lo, hi, dep, al, ah, bl, bh:
                    one(h, g, hh, c, None, po, fm, None, lo, hi, dep,
                        (al, ah, bl, bh)))(
                    histk, pgk, phk, pck, parent_outk, fmaskk, lok, hik,
                    depthk, *advk)
            return jax.vmap(
                lambda h, g, hh, c, pe, po, fm, lo, hi, dep, al, ah, bl, bh:
                one(h, g, hh, c, pe, po, fm, None, lo, hi, dep,
                    (al, ah, bl, bh)))(
                histk, pgk, phk, pck, penaltyk, parent_outk, fmaskk, lok,
                hik, depthk, *advk)
        if penaltyk is None and randk is None:
            return jax.vmap(
                lambda h, g, hh, c, po, fm, lo, hi, dep: one(
                    h, g, hh, c, None, po, fm, None, lo, hi, dep))(
                histk, pgk, phk, pck, parent_outk, fmaskk, lok, hik, depthk)
        if penaltyk is None:
            return jax.vmap(
                lambda h, g, hh, c, po, fm, rb, lo, hi, dep: one(
                    h, g, hh, c, None, po, fm, rb, lo, hi, dep))(
                histk, pgk, phk, pck, parent_outk, fmaskk, randk, lok, hik,
                depthk)
        if randk is None:
            return jax.vmap(
                lambda h, g, hh, c, pe, po, fm, lo, hi, dep: one(
                    h, g, hh, c, pe, po, fm, None, lo, hi, dep))(
                histk, pgk, phk, pck, penaltyk, parent_outk, fmaskk, lok,
                hik, depthk)
        return jax.vmap(one)(histk, pgk, phk, pck, penaltyk, parent_outk,
                             fmaskk, randk, lok, hik, depthk)

    _best_for_pair = _best_for_batch

    if n_forced and (cfg.leaf_batch > 1 or cfg.voting):
        raise ValueError(
            "forced splits require leaf_batch=1 and are not supported with "
            "voting-parallel (the wave scheduler would reorder them)")
    fp_axis_name = None
    fp_shards = 1
    if static.layout == "feature":
        fp_axis_name = next(a for a in mesh.axis_names if a != data_axis)
        fp_shards = int(mesh.shape[fp_axis_name])

    adv = cfg.mono_advanced and cfg.split.has_monotone
    inter = (cfg.mono_intermediate or adv) and cfg.split.has_monotone
    rs_shards = 1 if mesh is None else int(mesh.shape[data_axis])
    _W_FRONTIER = min(cfg.leaf_batch, max(L - 1, 1))

    def _pool_slots(hist_cols: int) -> int:
        """Static slot count for a pool over (hist_cols, HB, 3) 4-byte
        slots under the reference's MB semantics, clamped so one wave
        always fits (W parent slots stay pinned for sibling subtraction
        while up to 2W child slots materialize) and to L (>= L slots ==
        today's unpooled carry, returned as exactly L).  (Reference
        HistogramPool, serial_tree_learner.h: cache_size slots, LRU
        eviction, recompute on a cache miss.)"""
        if not static.pool:
            return L
        slot_bytes = hist_cols * HB * 3 * 4
        p = int(float(cfg.histogram_pool_size) * (1 << 20)
                // max(slot_bytes, 1))
        floor = min(2 * _W_FRONTIER + 1, L)
        return min(max(p, floor), L)

    def _pool_ops(P):
        """Slot machinery for a P-slot pool: LRU claim/evict and ownership
        bookkeeping."""
        IMAX = jnp.iinfo(jnp.int32).max

        @phase("grow/update")
        def claim(st, sp, active, miss):
            """Claim pool slots for W splitting leaves: each active leaf j
            needs one fresh slot for its smaller child's histogram; the
            larger child reuses the parent's slot ``sp[j]`` (the sibling
            subtraction lands in place, the reference's
            ``FeatureHistogram::Subtract`` into the parent's pool entry) —
            or a second fresh slot when the parent's histogram was evicted
            (``miss``).  Free slots are claimed first, then the least-
            recently-stamped unpinned slot; parents of this wave and
            already-claimed slots are pinned.  Returns
            ``(st, slot_small (W,), slot_big (W,))`` with evicted leaves'
            ``leaf_slot`` cleared; sentinel P marks inactive lanes."""
            Wc = sp.shape[0]
            pin0 = jnp.zeros(P + 1, bool).at[
                jnp.where(active & (sp >= 0), sp, P)].set(True)[:P]
            base = jnp.where(st.slot_leaf < 0, jnp.int32(-1), st.slot_tick)

            def claim_one(j, carry):
                pin, ss, sb, ev = carry
                key = jnp.where(pin, IMAX, base)
                v1 = jnp.argmin(key).astype(jnp.int32)
                key2 = jnp.where(jnp.arange(P) == v1, IMAX, key)
                v2 = jnp.argmin(key2).astype(jnp.int32)
                act, use2 = active[j], miss[j]
                pin_n = pin.at[v1].set(True)
                pin_n = jnp.where(use2, pin_n.at[v2].set(True), pin_n)
                pin = jnp.where(act, pin_n, pin)
                ev = ev.at[2 * j].set(jnp.where(act, st.slot_leaf[v1], -1))
                ev = ev.at[2 * j + 1].set(
                    jnp.where(act & use2, st.slot_leaf[v2], -1))
                ss = ss.at[j].set(jnp.where(act, v1, P))
                sb = sb.at[j].set(
                    jnp.where(act, jnp.where(use2, v2, sp[j]), P))
                return pin, ss, sb, ev

            _, ss, sb, ev = jax.lax.fori_loop(
                0, Wc, claim_one,
                (pin0, jnp.zeros(Wc, jnp.int32), jnp.zeros(Wc, jnp.int32),
                 jnp.full(2 * Wc, -1, jnp.int32)))
            leaf_slot = st.leaf_slot.at[
                jnp.where(ev >= 0, ev, L)].set(-1, mode="drop")
            return st._replace(leaf_slot=leaf_slot), ss, sb

        @phase("grow/update")
        def assign(st, children, slots):
            """Record ownership + LRU stamps for 2W (child leaf, slot)
            pairs; sentinel indices (leaf >= L / slot >= P) drop."""
            return st._replace(
                leaf_slot=st.leaf_slot.at[children].set(slots, mode="drop"),
                slot_leaf=st.slot_leaf.at[slots].set(children, mode="drop"),
                slot_tick=st.slot_tick.at[slots].set(st.tick, mode="drop"),
                tick=st.tick + 1)

        return claim, assign

    def _pool_setup(pool_cols, axis, rs):
        """Per-layout pool context of _grow_wave:
        slot count, activity flag, claim/assign ops, and the reduce every
        recomputed (miss) histogram must ride so its value matches the
        resident path's."""
        P = _pool_slots(pool_cols)
        pool_on = P < L
        pool_claim, pool_assign = _pool_ops(P) if pool_on else (None, None)

        @phase("grow/reduce")
        def reduce_hist(h):
            if axis is None:
                return h
            return rs["scatter"](h) if rs is not None \
                else _psum(h, axis)

        return P, pool_on, pool_claim, pool_assign, reduce_hist
    if inter and cfg.voting:
        raise ValueError(
            "monotone_constraints_method=intermediate/advanced does not "
            "compose with tree_learner=voting (the refresh needs the full "
            "leaf histograms resident, voting keeps them local)")
    if inter and need_key:
        raise ValueError(
            "monotone_constraints_method=intermediate/advanced does not "
            "compose with extra_trees / feature_fraction_bynode (the "
            "per-step best-split refresh would re-draw their per-node "
            "randomness)")
    if adv and cfg.mono_static is None:
        raise ValueError("mono_advanced requires the static "
                         "monotone-constraint vector (mono_static)")
    if adv and n_forced:
        raise ValueError(
            "monotone_constraints_method=advanced does not compose with "
            "forced splits (the refresh-gathered child bounds would not "
            "match a force-overwritten split); use intermediate")
    if cfg.packed4 and not static.packed4:
        raise ValueError("packed4 bins: " + static.why["packed4"]
                         + " (the caller packs by its plan)")
    @phase("grow/scan")
    def _vote_best_batch(hist_loc, pgk, phk, pck, poutk, scale3, meta,
                         feature_mask, boundsk, depthk, axis,
                         penaltyk=None, key=None, pathk=None,
                         groups_mat=None):
        """Voting-parallel split search for k children (reference
        ``GlobalVoting`` + ``SyncUpHistograms``,
        ``voting_parallel_tree_learner.cpp``): each shard votes its local
        top-k features by LOCAL split gain; only the global top-2k features'
        histogram slices are psum'd, then the real split search runs on the
        compact global slices.

        Per-node randomness (extra_trees thresholds, bynode feature masks),
        interaction constraints, and CEGB penalties compose: the node key
        and penalties are replicated across shards, so every shard draws
        the SAME masks/thresholds and votes stay consistent (the
        reference's learners compose the same options orthogonally,
        tree_learner.cpp:31-44)."""
        nbpf, nan_bins, is_cat, monotone = meta[:4]
        k_child, f = hist_loc.shape[0], meta[0].shape[0]
        kk = min(cfg.vote_top_k, f)
        sel_k = min(2 * kk, f)
        hist_loc_s = _scale_hist(hist_loc, scale3)
        loc_tot = jnp.sum(hist_loc_s[:, 0], axis=1)            # (k, 3)
        # EFB: expansion is linear in the histogram, so psum of expanded
        # slices equals expansion of psum'd slices — F-space throughout.
        hist_loc_s = _expand_hist_batch(hist_loc_s, meta, loc_tot[:, 0],
                                        loc_tot[:, 1], loc_tot[:, 2])
        if depthk is None:
            depthk = jnp.zeros(k_child, jnp.int32)
        if boundsk is None:
            lok = hik = jnp.zeros(k_child, jnp.float32)
            use_b = False
        else:
            lok, hik = boundsk
            use_b = True
        fmaskk, randk = _node_scan_inputs(key, feature_mask, nbpf,
                                          k_child, pathk, groups_mat)
        has_rand = randk is not None
        has_pen = penaltyk is not None
        randk_ = randk if has_rand else jnp.zeros((k_child, 1), jnp.int32)
        penk_ = (penaltyk if has_pen
                 else jnp.zeros((k_child, 1), jnp.float32))

        def local_gains(h, g, hh, c, fm, rb, pen):
            _, fg = best_split(
                h, g, hh, c, num_bins_per_feature=nbpf, nan_bins=nan_bins,
                is_categorical=is_cat, monotone=monotone,
                feature_mask=fm, cfg=cfg.split,
                rand_bins=rb if has_rand else None,
                gain_penalty=pen if has_pen else None,
                with_feature_gains=True)
            return fg

        fg = jax.vmap(local_gains)(hist_loc_s, loc_tot[:, 0],
                                   loc_tot[:, 1], loc_tot[:, 2],
                                   fmaskk, randk_, penk_)          # (k, F)
        _, top_idx = jax.lax.top_k(fg, kk)
        votes = jnp.zeros((k_child, f), jnp.int32).at[
            jnp.arange(k_child)[:, None], top_idx].add(1)
        votes = _psum(votes, axis)
        gsum = _psum(jnp.where(jnp.isfinite(fg), fg, 0.0), axis)
        # Rank by votes with gain strictly as tie-break (reference
        # GlobalVoting orders by vote count): normalize gains into [0, 1)
        # so they can never outweigh one vote.
        gmax = jnp.max(gsum, axis=-1, keepdims=True)
        tie = jnp.where(gmax > 0.0,
                        gsum / jnp.maximum(gmax * (1.0 + 1e-6), 1e-30), 0.0)
        score = votes.astype(jnp.float32) + tie
        _, sel = jax.lax.top_k(score, sel_k)           # (k, 2k) replicated
        if cfg.bundled:
            # expansion already happened (linear, psum-compatible)
            hist_sel = jnp.take_along_axis(
                hist_loc_s, sel[:, :, None, None], axis=1)
            hist_sel = _psum(hist_sel, axis)    # ONLY winners cross
        else:
            # psum the RAW slices (integer tensors under quantized
            # training, bin.h:48-81); scale after the reduce.
            hist_sel = jnp.take_along_axis(
                hist_loc, sel[:, :, None, None], axis=1)
            hist_sel = _scale_hist(_psum(hist_sel, axis), scale3)

        def one(h, pg, ph, pc, po, selj, lo, hi, dep, fm, rb, pen):
            bs = best_split(
                h, pg, ph, pc,
                num_bins_per_feature=nbpf[selj], nan_bins=nan_bins[selj],
                is_categorical=is_cat[selj], monotone=monotone[selj],
                feature_mask=fm[selj], cfg=cfg.split,
                rand_bins=rb[selj] if has_rand else None,
                gain_penalty=pen[selj] if has_pen else None,
                parent_output=po,
                out_lo=lo if use_b else None,
                out_hi=hi if use_b else None,
                leaf_depth=dep)
            return bs._replace(feature=selj[bs.feature])

        return jax.vmap(one)(hist_sel, pgk, phk, pck, poutk, sel, lok, hik,
                             depthk, fmaskk, randk_, penk_)

    def _cegb_penalty(count, feat_used, path_used, coupled, lazy):
        """Per-feature gain penalty (reference CEGB ``DeltaGain``):
        tradeoff * (penalty_split*count + coupled[f]*first-use-in-model
        + lazy[f]*rows-not-yet-scanned).  Lazy uses per-leaf path tracking
        (exact within a tree; the reference's cross-tree per-row bitset is
        approximated by the path of the current tree)."""
        if not cfg.split.use_cegb:
            return None
        t = cfg.split.cegb_tradeoff
        pen = jnp.full_like(coupled, t * cfg.split.cegb_penalty_split * count)
        pen = pen + t * coupled * (~feat_used)
        pen = pen + t * lazy * count * (~path_used)
        return pen

    def _init_state(n, f, gcols, root_hist, root_g, root_h, root_c,
                    key=None, pool_slots=None):
        tree = TreeArrays(
            split_feature=jnp.zeros(M, jnp.int32),
            split_bin=jnp.zeros(M, jnp.int32),
            default_left=jnp.zeros(M, bool),
            is_cat=jnp.zeros(M, bool),
            cat_mask=jnp.zeros((M, B), bool),
            left_child=jnp.zeros(M, jnp.int32),
            right_child=jnp.zeros(M, jnp.int32),
            split_gain=jnp.zeros(M, jnp.float32),
            internal_value=jnp.zeros(M, jnp.float32),
            internal_count=jnp.zeros(M, jnp.float32),
            leaf_value=jnp.zeros(L, jnp.float32),
            leaf_count=jnp.zeros(L, jnp.float32),
            leaf_weight=jnp.zeros(L, jnp.float32),
            num_leaves=jnp.asarray(1, jnp.int32),
        )
        P = L if pool_slots is None else pool_slots
        pooled = P < L
        registry().gauge("grow.leaf_hist_bytes").set(
            P * gcols * HB * 3 * jnp.dtype(root_hist.dtype).itemsize)
        return _GrowState(
            num_leaves=jnp.asarray(1, jnp.int32),
            perm=jnp.zeros(0, jnp.int32),  # set by caller when used
            leaf_start=jnp.zeros(L, jnp.int32),
            leaf_rows=jnp.zeros(L, jnp.int32).at[0].set(n),
            leaf_hist=jnp.zeros((P, gcols, HB, 3),
                                root_hist.dtype).at[0].set(root_hist),
            leaf_slot=(jnp.full(L, -1, jnp.int32).at[0].set(0) if pooled
                       else jnp.zeros(1, jnp.int32)),
            slot_leaf=(jnp.full(P, -1, jnp.int32).at[0].set(0) if pooled
                       else jnp.zeros(1, jnp.int32)),
            slot_tick=jnp.zeros(P if pooled else 1, jnp.int32),
            tick=jnp.asarray(1, jnp.int32),
            leaf_sum_grad=jnp.zeros(L, jnp.float32).at[0].set(root_g),
            leaf_sum_hess=jnp.zeros(L, jnp.float32).at[0].set(root_h),
            leaf_count=jnp.zeros(L, jnp.float32).at[0].set(root_c),
            leaf_depth=jnp.zeros(L, jnp.int32),
            leaf_parent=jnp.full(L, -1, jnp.int32),
            leaf_is_left=jnp.zeros(L, bool),
            best_gain=jnp.full(L, _NEG_INF, jnp.float32),
            best_feature=jnp.zeros(L, jnp.int32),
            best_bin=jnp.zeros(L, jnp.int32),
            best_default_left=jnp.zeros(L, bool),
            best_is_cat=jnp.zeros(L, bool),
            best_cat_mask=jnp.zeros((L, B), bool),
            best_gl=jnp.zeros(L, jnp.float32),
            best_hl=jnp.zeros(L, jnp.float32),
            best_cl=jnp.zeros(L, jnp.float32),
            leaf_out=jnp.zeros(L, jnp.float32).at[0].set(
                leaf_output(root_g, root_h, cfg.split)),
            leaf_lo=jnp.full(L, -jnp.inf, jnp.float32),
            leaf_hi=jnp.full(L, jnp.inf, jnp.float32),
            feat_used=jnp.zeros(f, bool),
            leaf_path=jnp.zeros((L, f), bool),
            rng=(key if key is not None
                 else jnp.zeros(2, jnp.uint32)),
            forced_leaf=jnp.zeros(max(n_forced, 1), jnp.int32),
            leaf_bin_lo=jnp.zeros((L, f) if inter else (1, 1), jnp.int32),
            leaf_bin_hi=(jnp.full((L, f), B, jnp.int32) if inter
                         else jnp.ones((1, 1), jnp.int32)),
            adv_llo=jnp.full(L if adv else 1, -jnp.inf, jnp.float32),
            adv_lhi=jnp.full(L if adv else 1, jnp.inf, jnp.float32),
            adv_rlo=jnp.full(L if adv else 1, -jnp.inf, jnp.float32),
            adv_rhi=jnp.full(L if adv else 1, jnp.inf, jnp.float32),
            tree=tree,
        )

    @phase("grow/update")
    def _update_tree(st: _GrowState, leaf, new_leaf, node, pg, ph, pc):
        """Shared tree bookkeeping for one executed split."""
        tr = st.tree
        feat = st.best_feature[leaf]
        parent = st.leaf_parent[leaf]
        p_safe = jnp.maximum(parent, 0)
        was_left = st.leaf_is_left[leaf]
        left_child = tr.left_child.at[p_safe].set(
            jnp.where((parent >= 0) & was_left, node, tr.left_child[p_safe]))
        right_child = tr.right_child.at[p_safe].set(
            jnp.where((parent >= 0) & ~was_left, node, tr.right_child[p_safe]))
        return tr._replace(
            split_feature=tr.split_feature.at[node].set(feat),
            split_bin=tr.split_bin.at[node].set(st.best_bin[leaf]),
            default_left=tr.default_left.at[node].set(st.best_default_left[leaf]),
            is_cat=tr.is_cat.at[node].set(st.best_is_cat[leaf]),
            cat_mask=tr.cat_mask.at[node].set(st.best_cat_mask[leaf]),
            left_child=left_child.at[node].set(~leaf),
            right_child=right_child.at[node].set(~new_leaf),
            split_gain=tr.split_gain.at[node].set(st.best_gain[leaf]),
            internal_value=tr.internal_value.at[node].set(st.leaf_out[leaf]),
            internal_count=tr.internal_count.at[node].set(pc),
        )

    @phase("grow/finish")
    def _finish(state: _GrowState) -> TreeArrays:
        leaf_ids = jnp.arange(L)
        active = leaf_ids < state.num_leaves
        # leaf_out carries the (possibly path-smoothed) output chain; without
        # smoothing it equals leaf_output(sum_grad, sum_hess) exactly.
        values = state.leaf_out
        return state.tree._replace(
            leaf_value=jnp.where(active, values, 0.0),
            leaf_count=jnp.where(active, state.leaf_count, 0.0),
            leaf_weight=jnp.where(active, state.leaf_sum_hess, 0.0),
            num_leaves=state.num_leaves,
        )

    @phase("grow/update")
    def _children_updates(st, leaf, new_leaf, hist_left, hist_right,
                          gl, hl, cl, gr, hr, cr, meta, feature_mask,
                          cegb=None, groups_mat=None, scale3=None):
        """Store child stats + their best splits (both children batched into
        single 2-row scatters to minimize kernel count in the hot loop):
        one executed split of the mask body and of the stream kit."""
        depth = st.leaf_depth[leaf] + 1
        node = st.num_leaves - 1
        pair = jnp.stack([leaf, new_leaf])
        parent_out = st.leaf_out[leaf]
        out_l = smoothed_output(gl, hl, cl, parent_out, cfg.split)
        out_r = smoothed_output(gr, hr, cr, parent_out, cfg.split)
        bounds2 = None
        depth2 = jnp.stack([st.leaf_depth[leaf] + 1,
                            st.leaf_depth[leaf] + 1])
        if cfg.split.has_monotone:
            plo, phi = st.leaf_lo[leaf], st.leaf_hi[leaf]
            if adv:
                # Advanced mode: the executed split IS the stored best split,
                # so clip each child to its refresh-gathered per-threshold
                # bound (looser-or-equal than the whole-leaf scalar).
                out_l = jnp.clip(out_l, st.adv_llo[leaf], st.adv_lhi[leaf])
                out_r = jnp.clip(out_r, st.adv_rlo[leaf], st.adv_rhi[leaf])
            else:
                out_l = jnp.clip(out_l, plo, phi)
                out_r = jnp.clip(out_r, plo, phi)
            if inter:
                # Intermediate mode: children inherit the parent's bounds
                # verbatim; the real bounds (and every leaf's refreshed
                # best split) come from _inter_refresh right after this
                # split.  Track the children's bin rectangles for the
                # adjacency pass.
                feat = st.best_feature[leaf]
                is_num = ~st.best_is_cat[leaf]
                cut = st.best_bin[leaf] + 1
                lo_p = st.leaf_bin_lo[leaf]
                hi_p = st.leaf_bin_hi[leaf]
                fhot1 = jnp.arange(lo_p.shape[0]) == feat
                hi_l_r = jnp.where(fhot1 & is_num,
                                   jnp.minimum(hi_p, cut), hi_p)
                lo_r_r = jnp.where(fhot1 & is_num,
                                   jnp.maximum(lo_p, cut), lo_p)
                st = st._replace(
                    leaf_bin_lo=st.leaf_bin_lo.at[pair].set(
                        jnp.stack([lo_p, lo_r_r])),
                    leaf_bin_hi=st.leaf_bin_hi.at[pair].set(
                        jnp.stack([hi_l_r, hi_p])),
                    leaf_lo=st.leaf_lo.at[pair].set(jnp.stack([plo, plo])),
                    leaf_hi=st.leaf_hi.at[pair].set(jnp.stack([phi, phi])))
                bounds2 = (jnp.stack([plo, plo]), jnp.stack([phi, phi]))
            else:
                # Basic monotone bounds (reference
                # BasicLeafConstraints::Update,
                # monotone_constraints.hpp:487): a numerical split on a
                # monotone feature caps both children at the child-output
                # midpoint; outputs are always clipped to the leaf's
                # inherited bounds.
                mono_t = meta[3][st.best_feature[leaf]]
                is_num = ~st.best_is_cat[leaf]
                mid = (out_l + out_r) / 2.0
                lo_l = jnp.where((mono_t < 0) & is_num,
                                 jnp.maximum(plo, mid), plo)
                hi_l = jnp.where((mono_t > 0) & is_num,
                                 jnp.minimum(phi, mid), phi)
                lo_r = jnp.where((mono_t > 0) & is_num,
                                 jnp.maximum(plo, mid), plo)
                hi_r = jnp.where((mono_t < 0) & is_num,
                                 jnp.minimum(phi, mid), phi)
                st = st._replace(
                    leaf_lo=st.leaf_lo.at[pair].set(jnp.stack([lo_l, lo_r])),
                    leaf_hi=st.leaf_hi.at[pair].set(jnp.stack([hi_l, hi_r])))
                bounds2 = (jnp.stack([lo_l, lo_r]), jnp.stack([hi_l, hi_r]))
        node_key = None
        if need_key:
            rng, node_key = jax.random.split(st.rng)
            st = st._replace(rng=rng)
        penalty2 = None
        path2 = None
        if track_path:
            feat = st.best_feature[leaf]
            fhot = jnp.arange(st.feat_used.shape[0]) == feat
            child_path = st.leaf_path[leaf] | fhot
            path2 = jnp.stack([child_path, child_path])
            st = st._replace(leaf_path=st.leaf_path.at[pair].set(path2))
        if cfg.split.use_cegb and cegb is not None:
            coupled, lazy = cegb
            feat_used = st.feat_used | fhot
            st = st._replace(feat_used=feat_used)
            penalty2 = jnp.stack([
                _cegb_penalty(cl, feat_used, child_path, coupled, lazy),
                _cegb_penalty(cr, feat_used, child_path, coupled, lazy),
            ])
        hist2 = jnp.stack([hist_left, hist_right])     # RAW (stored)
        g2 = jnp.stack([gl, gr])
        h2 = jnp.stack([hl, hr])
        c2 = jnp.stack([cl, cr])
        hist2s = _expand_hist_batch(_scale_hist(hist2, scale3), meta,
                                    g2, h2, c2)        # scaled (split scan)
        st = st._replace(
            num_leaves=st.num_leaves + 1,
            leaf_hist=st.leaf_hist.at[pair].set(hist2),
            leaf_sum_grad=st.leaf_sum_grad.at[pair].set(g2),
            leaf_sum_hess=st.leaf_sum_hess.at[pair].set(h2),
            leaf_count=st.leaf_count.at[pair].set(c2),
            leaf_depth=st.leaf_depth.at[pair].set(jnp.stack([depth, depth])),
            leaf_parent=st.leaf_parent.at[pair].set(jnp.stack([node, node])),
            leaf_is_left=st.leaf_is_left.at[pair].set(
                jnp.asarray([True, False])),
            leaf_out=st.leaf_out.at[pair].set(jnp.stack([out_l, out_r])),
        )
        depth_ok = jnp.asarray(True) if cfg.max_depth <= 0 \
            else depth < cfg.max_depth
        bs2 = _best_for_pair(hist2s, g2, h2, c2, meta, feature_mask,
                             penalty2, jnp.stack([out_l, out_r]), node_key,
                             path2, groups_mat, bounds2, depth2)
        gain2 = jnp.where(depth_ok, bs2.gain, _NEG_INF)
        return st._replace(
            best_gain=st.best_gain.at[pair].set(gain2),
            best_feature=st.best_feature.at[pair].set(bs2.feature),
            best_bin=st.best_bin.at[pair].set(bs2.bin),
            best_default_left=st.best_default_left.at[pair].set(
                bs2.default_left),
            best_is_cat=st.best_is_cat.at[pair].set(bs2.is_cat),
            best_cat_mask=st.best_cat_mask.at[pair].set(bs2.cat_mask),
            best_gl=st.best_gl.at[pair].set(bs2.sum_grad_left),
            best_hl=st.best_hl.at[pair].set(bs2.sum_hess_left),
            best_cl=st.best_cl.at[pair].set(bs2.count_left),
        )

    def _adv_threshold_bounds(st):
        """Advanced monotone mode: dense per-threshold child output bounds.

        Reference ``AdvancedLeafConstraints`` (monotone_constraints.hpp:583)
        keeps per-(leaf, feature) lists of (threshold, constraint) slices
        with cumulative min/max arrays (``CumulativeFeatureConstraint``) so
        each candidate threshold sees only the constraints of neighbours
        actually adjacent to the would-be child.  The TPU shape: four dense
        (L, F, B) tensors — lower/upper output bounds for the left/right
        child at every (leaf, split feature, threshold) — built from the
        leaf bin-rectangles by scatter-min/max keyed on neighbour edges plus
        cummin/cummax along the bin axis (the cumulative-extremum arrays).

        Soundness: a bound slice accounts for EVERY alive leaf wholly on the
        child's output-increasing (resp. decreasing) side along some
        monotone feature g while overlapping the child's rectangle in all
        other features.  Distinct leaves are disjoint, so threshold
        dependence enters only through the child's extent in the split
        dimension: for the edge that moves with the threshold the
        constraint set grows monotonically in t (a prefix/suffix extremum);
        for the fixed edge it is threshold-independent."""
        lo, hi = st.leaf_bin_lo, st.leaf_bin_hi     # (L, F) i32
        out = st.leaf_out
        f = lo.shape[1]
        iL = jnp.arange(L)
        alive = iL < st.num_leaves
        ov = ((lo[:, None, :] < hi[None, :, :])
              & (lo[None, :, :] < hi[:, None, :]))  # (L, L, F)
        ovi = ov.astype(jnp.int32)
        n_ov = jnp.sum(ovi, axis=-1)                # (L, L)
        pairm = alive[:, None] & alive[None, :] & (iL[:, None] != iL[None, :])
        outJ = jnp.broadcast_to(out[None, :], (L, L))
        INF = jnp.inf
        LLO = jnp.full((L, f, B), -INF, jnp.float32)
        LHI = jnp.full((L, f, B), INF, jnp.float32)
        RLO = jnp.full((L, f, B), -INF, jnp.float32)
        RHI = jnp.full((L, f, B), INF, jnp.float32)

        def sufmin(x):
            return jnp.flip(jax.lax.cummin(jnp.flip(x, -1), axis=x.ndim - 1), -1)

        def sufmax(x):
            return jnp.flip(jax.lax.cummax(jnp.flip(x, -1), axis=x.ndim - 1), -1)

        def shift_next(x, fill):
            # y[..., t] = x[..., t+1]; the last column gets ``fill``
            pad = jnp.full(x.shape[:-1] + (1,), fill, x.dtype)
            return jnp.concatenate([x[..., 1:], pad], axis=-1)

        I2 = jnp.broadcast_to(iL[:, None], (L, L))

        def scat2_min(key_j, vals):
            # S[i, b] = min over j with key_j[j] == b of vals[i, j]
            K = jnp.broadcast_to(key_j[None, :], (L, L))
            return jnp.full((L, B), INF, jnp.float32).at[I2, K].min(vals)

        def scat2_max(key_j, vals):
            K = jnp.broadcast_to(key_j[None, :], (L, L))
            return jnp.full((L, B), -INF, jnp.float32).at[I2, K].max(vals)

        sh3 = (L, L, f)
        I3 = jnp.broadcast_to(iL[:, None, None], sh3)
        S3 = jnp.broadcast_to(jnp.arange(f)[None, None, :], sh3)

        def scat3_min(key_js, vals):
            # S[i, s, b] = min over j with key_js[j, s] == b of vals[i, j, s]
            K = jnp.broadcast_to(key_js[None, :, :], sh3)
            return jnp.full((L, f, B), INF, jnp.float32).at[I3, S3, K] \
                .min(vals)

        def scat3_max(key_js, vals):
            K = jnp.broadcast_to(key_js[None, :, :], sh3)
            return jnp.full((L, f, B), -INF, jnp.float32).at[I3, S3, K] \
                .max(vals)

        key_lo = jnp.clip(lo, 0, B - 1)             # per-j edge keys (L, F)
        key_hi = jnp.clip(hi - 1, 0, B - 1)

        for g, mg in enumerate(cfg.mono_static):
            if mg == 0:
                continue
            # j wholly above / below leaf i along g (spatially)
            j_above = hi[:, None, g] <= lo[None, :, g]          # (L, L)
            j_below = hi[None, :, g] <= lo[:, None, g]

            # ---- split feature s == g: the child's extent along g moves
            # with the threshold.  Disjointness makes the keyed scatters
            # subsume the whole-leaf case for the moving edge; the fixed
            # edge contributes a threshold-independent extremum.
            othersA = pairm & ((n_ov - ovi[:, :, g]) == f - 1)
            vminA = jnp.where(othersA, outJ, INF)
            vmaxA = jnp.where(othersA, outJ, -INF)
            if mg > 0:
                # LEFT child [lo_i, t+1): j with lo_j >= t+1 upper-bounds it
                LHI = LHI.at[:, g, :].min(
                    shift_next(sufmin(scat2_min(key_lo[:, g], vminA)), INF))
                # RIGHT child [t+1, hi_i): j with hi_j <= t+1 lower-bounds it
                RLO = RLO.at[:, g, :].max(
                    jax.lax.cummax(scat2_max(key_hi[:, g], vmaxA), axis=1))
                # fixed edges: j above the whole leaf caps the right child;
                # j below floors the left child
                up_c = jnp.where(othersA & j_above, outJ, INF).min(axis=1)
                dn_c = jnp.where(othersA & j_below, outJ, -INF).max(axis=1)
                RHI = RHI.at[:, g, :].min(up_c[:, None])
                LLO = LLO.at[:, g, :].max(dn_c[:, None])
            else:
                # mg < 0: j above lower-bounds, j below upper-bounds
                LLO = LLO.at[:, g, :].max(
                    shift_next(sufmax(scat2_max(key_lo[:, g], vmaxA)), -INF))
                RHI = RHI.at[:, g, :].min(
                    jax.lax.cummin(scat2_min(key_hi[:, g], vminA), axis=1))
                dn_c = jnp.where(othersA & j_above, outJ, -INF).max(axis=1)
                up_c = jnp.where(othersA & j_below, outJ, INF).min(axis=1)
                RLO = RLO.at[:, g, :].max(dn_c[:, None])
                LHI = LHI.at[:, g, :].min(up_c[:, None])

            # ---- split feature s != g: the side along g is fixed (the
            # child keeps the leaf's g-extent); the threshold only governs
            # whether j still overlaps the child's s-extent.
            upJ = (j_above if mg > 0 else j_below)[:, :, None]
            dnJ = (j_below if mg > 0 else j_above)[:, :, None]
            othersB = (n_ov[:, :, None] - ovi[:, :, g][:, :, None]
                       - ovi) == f - 2                          # (L, L, F)
            smask = (jnp.arange(f) != g)[None, None, :]
            baseB = pairm[:, :, None] & othersB & smask
            # LEFT child keeps [lo_i_s, t+1): j needs hi_j_s > lo_i_s
            # (t-independent) and lo_j_s <= t (prefix along the bin axis)
            qual_l = baseB & (hi[None, :, :] > lo[:, None, :])
            # RIGHT child keeps [t+1, hi_i_s): j needs lo_j_s < hi_i_s and
            # hi_j_s >= t+2 (suffix)
            qual_r = baseB & (lo[None, :, :] < hi[:, None, :])
            o3 = outJ[:, :, None]
            LHI = jnp.minimum(LHI, jax.lax.cummin(
                scat3_min(key_lo, jnp.where(qual_l & upJ, o3, INF)),
                axis=2))
            RHI = jnp.minimum(RHI, shift_next(sufmin(
                scat3_min(key_hi, jnp.where(qual_r & upJ, o3, INF))), INF))
            LLO = jnp.maximum(LLO, jax.lax.cummax(
                scat3_max(key_lo, jnp.where(qual_l & dnJ, o3, -INF)),
                axis=2))
            RLO = jnp.maximum(RLO, shift_next(sufmax(
                scat3_max(key_hi, jnp.where(qual_r & dnJ, o3, -INF))),
                -INF))
        return LLO, LHI, RLO, RHI

    def _pair_up(st, mono):
        """(L, L) bool: out_j upper-bounds leaf i's future children — j sits
        wholly on i's output-increasing side along some monotone feature
        while overlapping i in every other dimension.  The vectorized
        equivalent of the reference's GoUpToFindLeavesToUpdate contiguity
        walk, shared by the per-step refresh and the wave conflict
        filter."""
        f = mono.shape[0]
        lo_r, hi_r = st.leaf_bin_lo, st.leaf_bin_hi            # (L, F)
        alive = jnp.arange(L) < st.num_leaves
        o_lo, o_hi = lo_r[:, None, :], hi_r[:, None, :]
        t_lo, t_hi = lo_r[None, :, :], hi_r[None, :, :]
        overlap = (o_lo < t_hi) & (t_lo < o_hi)                # (L, L, F)
        n_overlap = jnp.sum(overlap, axis=-1)                  # (L, L)
        # pair (i, j) is adjacent along f iff their rectangles overlap in
        # every OTHER feature dimension
        adj = (n_overlap[:, :, None]
               - overlap.astype(jnp.int32)) == (f - 1)
        inc = (mono > 0)[None, None, :]
        dec = (mono < 0)[None, None, :]
        upper = adj & ((inc & (o_hi <= t_lo)) | (dec & (t_hi <= o_lo)))
        return jnp.any(upper, axis=-1) & alive[:, None] & alive[None, :]

    @phase("grow/scan")
    def _inter_refresh(st, scale3, meta, feature_mask, cegb=None,
                       groups_mat=None):
        """Intermediate monotone mode, per-step bound + best-split refresh.

        Reference ``IntermediateLeafConstraints`` (monotone_constraints.hpp:
        516) walks the tree recursively after each split
        (``GoUpToFindLeavesToUpdate``) to tighten the output bounds of
        leaves contiguous with the new children, then recomputes the best
        split of each touched leaf (``RecomputeBestSplitForLeaf``,
        serial_tree_learner.cpp:879).  With static shapes the TPU-shaped
        equivalent is: (1) ONE vectorized O(L^2 F) rectangle-adjacency pass
        deriving every leaf's bounds fresh from the CURRENT outputs of its
        feature-space neighbours — fresh derivation subsumes the reference's
        incremental min/max tightening and can only be looser-or-equal
        (= better splits) while preserving monotonicity; (2) ONE vmapped
        split rescan over ALL leaves from their resident histograms (the
        (L, F, B, 3) leaf_hist makes this a data-reuse win, not a rescan of
        rows)."""
        mono = meta[3]
        alive = jnp.arange(L) < st.num_leaves
        pair_up = _pair_up(st, mono)
        out = st.leaf_out
        new_hi = jnp.min(jnp.where(pair_up, out[None, :], jnp.inf), axis=1)
        new_lo = jnp.max(jnp.where(pair_up.T, out[None, :], -jnp.inf),
                         axis=1)
        st = st._replace(leaf_lo=new_lo, leaf_hi=new_hi)

        histL = _expand_hist_batch(
            _scale_hist(st.leaf_hist, scale3), meta, st.leaf_sum_grad,
            st.leaf_sum_hess, st.leaf_count)
        penaltyL = None
        if cfg.split.use_cegb and cegb is not None:
            coupled, lazy = cegb
            penaltyL = jax.vmap(
                lambda c, p: _cegb_penalty(c, st.feat_used, p, coupled,
                                           lazy))(st.leaf_count,
                                                  st.leaf_path)
        advk = _adv_threshold_bounds(st) if adv else None
        bs = _best_for_batch(
            histL, st.leaf_sum_grad, st.leaf_sum_hess, st.leaf_count, meta,
            feature_mask, penaltyL, st.leaf_out, None,
            st.leaf_path if track_path else None, groups_mat,
            (new_lo, new_hi), st.leaf_depth, advk=advk)
        if adv:
            # Record the refreshed best split's child bounds so the split
            # execution (_children_updates) clips each child to its
            # per-threshold slice; categorical winners fall back to the
            # scalar leaf bounds.
            gi = jnp.arange(L)

            def _at_best(arr, scalar_fb):
                return jnp.where(bs.is_cat, scalar_fb,
                                 arr[gi, bs.feature, bs.bin])

            st = st._replace(
                adv_llo=_at_best(advk[0], new_lo),
                adv_lhi=_at_best(advk[1], new_hi),
                adv_rlo=_at_best(advk[2], new_lo),
                adv_rhi=_at_best(advk[3], new_hi))
        depth_ok = (jnp.ones(L, bool) if cfg.max_depth <= 0
                    else st.leaf_depth < cfg.max_depth)
        gain = jnp.where(alive & depth_ok, bs.gain, _NEG_INF)
        return st._replace(
            best_gain=gain,
            best_feature=bs.feature,
            best_bin=bs.bin,
            best_default_left=bs.default_left,
            best_is_cat=bs.is_cat,
            best_cat_mask=bs.cat_mask,
            best_gl=bs.sum_grad_left,
            best_hl=bs.sum_hess_left,
            best_cl=bs.count_left,
        )

    def _scale_hist(hist, scale3):
        """Rescale an int32 quantized histogram to f32 (g, h, count) so the
        split scan downstream is layout-identical to the fp32 path."""
        if scale3 is None:
            return hist
        return hist.astype(jnp.float32) * scale3

    # SplitInfo payload broadcast globalizing slice-local winners — ONE
    # implementation (ops/split.py sync_best_split) shared by the
    # feature-parallel layout and the data-parallel reduce-scatter path so
    # their wire formats cannot diverge.
    _fp_sync_best = sync_best_split

    def _make_rs(axis, hist_cols, meta):
        """Per-shard context for the feature-sliced histogram reduce-scatter
        (``hist_comm=reduce_scatter``; reference
        ``data_parallel_tree_learner.cpp:284`` ReduceScatter + per-rank
        feature ownership).

        ``hist_cols`` is the HISTOGRAM feature-space width: G bundle columns
        under EFB, F otherwise (packed4 histograms are already unpacked to
        F).  Each shard owns the contiguous block
        ``[shard * go, (shard+1) * go)`` of that axis, ``go =
        ceil(hist_cols/shards)`` (histograms are zero-padded to ``gp = go *
        shards`` before the scatter; phantom columns have nbpf=0 so they can
        never win a scan).

        Returned dict:
        - ``scatter(h)``: (…, G, B, 3) local partials -> (…, go, B, 3) owned
          reduced block.  Under quantized training the wire payload drops to
          int16 (reference ``Int16HistogramSumReducer``, ``bin.h:48-81``)
          behind an exact-overflow guard: the psum of per-shard max-abs
          upper-bounds every partial sum of the reduction, so the int16
          branch can never wrap; otherwise the wire stays int32.
        - ``meta_s``: the 4 scan-meta arrays projected onto the owned slice
          (EFB keeps the full-F meta — the scan runs in expanded feature
          space with the ownership mask).
        - ``project(fm, rb, pen)``: per-node F-space inputs (feature mask /
          extra_trees thresholds / CEGB penalties, derived REPLICATED so
          every shard draws identical randomness) projected the same way.
        - ``sync(bs)``: the one-hot SplitInfo payload broadcast
          (``SyncUpGlobalBestSplit``) globalizing slice-local winners.
          Non-EFB slices are contiguous ascending feature blocks, so the
          lowest-shard tie-break reproduces the replicated scan's
          lowest-flat-index argmax exactly; under EFB ties break to the
          lowest OWNING shard (the reference's rank order).
        """
        from ..parallel.collectives import histogram_reduce_scatter_local

        go = -(-hist_cols // rs_shards)
        gp = go * rs_shards
        g_lo = (jax.lax.axis_index(axis) * go).astype(jnp.int32)

        @phase("grow/reduce")
        def scatter(h):
            d = h.ndim - 3                     # the feature axis of (…,G,B,3)
            if gp != hist_cols:
                pw = [(0, 0)] * h.ndim
                pw[d] = (0, gp - hist_cols)
                h = jnp.pad(h, pw)
            if cfg.quantized:
                # int16 wire format: sum-of-per-shard-maxes >= every partial
                # sum elementwise, so fitting int16 here is exact — no
                # overflow at any reduction step.  f32 compare is exact for
                # ints < 2^24; anything larger fails the guard anyway.
                bound = _psum(
                    jnp.max(jnp.abs(h)).astype(jnp.float32), axis)
                from ..resilience import faults
                if faults.active("overflow_hist"):
                    # fault seam (trace-time): classify every reduction as
                    # overflowing so the exact int32 fallback + the health
                    # report below run deterministically in tests
                    bound = bound + jnp.float32(65536.0)
                if cfg.health_signal:
                    # Promoted health signal (resilience/health.py): the
                    # silent int32 fallback now reports each escalation —
                    # a wire overflow means the quantized gradient scale
                    # no longer fits the shape and deserves triage, even
                    # though the fallback keeps the sums exact.
                    from ..resilience.health import record_hist_overflow
                    jax.debug.callback(record_hist_overflow,
                                       bound > 32767.0)
                return jax.lax.cond(
                    bound <= 32767.0,
                    lambda x: histogram_reduce_scatter_local(
                        x.astype(jnp.int16), axis, d).astype(jnp.int32),
                    lambda x: histogram_reduce_scatter_local(x, axis, d),
                    h)
            return histogram_reduce_scatter_local(h, axis, d)

        def _slice_last(a, pad_val):
            """Project an F-space array (…, F) onto the owned (…, go)
            window, padding phantom columns with ``pad_val``."""
            pad = gp - a.shape[-1]
            if pad:
                pw = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
                a = jnp.pad(a, pw, constant_values=pad_val)
            return jax.lax.dynamic_slice_in_dim(a, g_lo, go, axis=a.ndim - 1)

        if cfg.bundled:
            # Ownership in ORIGINAL-feature space: the features whose bundle
            # group falls inside the owned G block.  The scan stays full-F
            # (bundle members are not contiguous in F) with non-owned
            # features masked out; comm still shrinks by the scatter.
            own_f = (meta[4] >= g_lo) & (meta[4] < g_lo + go)
            meta_s = meta[:4]
            foff = jnp.zeros((), jnp.int32)

            def project(fm, rb=None, pen=None):
                return fm & own_f, rb, pen
        else:
            own_f = None
            meta_s = (_slice_last(meta[0], 0),       # nbpf=0: never valid
                      _slice_last(meta[1], HB),      # no NaN bin
                      _slice_last(meta[2], False),
                      _slice_last(meta[3], 0))
            foff = g_lo

            def project(fm, rb=None, pen=None):
                return (_slice_last(fm, False),
                        None if rb is None else _slice_last(rb, 0),
                        None if pen is None else _slice_last(pen, 0.0))

        return {
            "go": go, "gp": gp, "g_lo": g_lo, "own_f": own_f,
            "scatter": scatter, "meta_s": meta_s, "project": project,
            "sync": phase("grow/reduce")(
                lambda bs: _fp_sync_best(bs, foff, axis, rs_shards)),
        }

    def _fp_go_left(bins_pad, nan_bins, feat_g, sbin, dleft, scat, cmask,
                    foffset, fl, faxis):
        """Row routing for a GLOBAL split feature when each shard holds only
        its own feature columns: the owner computes the (N+1,) go-left
        vector, one psum broadcasts it (the reference avoids this by
        replicating the data; here it costs N bits per split and buys an
        S-fold bins/histogram memory + compute split)."""
        lf = feat_g - foffset
        owns = (lf >= 0) & (lf < fl)
        col = bins_pad[:, jnp.clip(lf, 0, fl - 1)].astype(jnp.int32)
        is_nan = col == nan_bins[jnp.clip(lf, 0, fl - 1)]
        gl = jnp.where(scat, cmask[col], col <= sbin)
        gl = jnp.where(is_nan & ~scat, dleft, gl)
        gl = jnp.where(owns, gl, False)
        return _psum(gl.astype(jnp.float32), faxis) > 0.5

    def _expand_hist(bh, meta, tg, th, tc, rs=None):
        """(G, B, 3) bundle histogram -> (F, B, 3) per-original-feature view
        (reference: per-feature offsets into group histograms,
        feature_histogram.hpp).  Bundled features' default bin 0 is
        reconstructed as leaf_total - sum(non-default bins).

        Under the reduce-scatter layout ``bh`` is this shard's owned
        (go, B, 3) group block; only owned features expand (the rest are
        zeroed and masked out of the scan by ``rs["project"]``)."""
        if not cfg.bundled:
            return bh
        nbpf, fg, fo = meta[0], meta[4], meta[5]
        own = None
        if rs is not None:
            own = rs["own_f"]
            fg = jnp.clip(fg - rs["g_lo"], 0, bh.shape[-3] - 1)
        b_iota = jnp.arange(B)
        ident = fo < 0
        src_bin = jnp.where(ident[:, None], b_iota[None, :],
                            fo[:, None] + b_iota[None, :] - 1)
        valid = ident[:, None] | ((b_iota[None, :] >= 1)
                                  & (b_iota[None, :] < nbpf[:, None]))
        src_bin = jnp.clip(src_bin, 0, bh.shape[-2] - 1)
        hf = bh[fg[:, None], src_bin, :] * valid[..., None]  # (F, B, 3)
        tot = jnp.stack([tg, th, tc])
        h0 = jnp.where(ident[:, None], hf[:, 0, :],
                       tot[None, :] - jnp.sum(hf, axis=1))
        out = hf.at[:, 0, :].set(h0)
        if own is not None:
            out = out * own[:, None, None].astype(out.dtype)
        return out

    def _expand_hist_batch(bhk, meta, gk, hk, ck, rs=None):
        if not cfg.bundled:
            return bhk
        return jax.vmap(lambda b, g, h, c: _expand_hist(b, meta, g, h, c,
                                                        rs))(
            bhk, gk, hk, ck)

    def _hist_branch_for(bins_pad, vals_pad, n, S, nf=0):
        """RAW histogram of a contiguous perm range of static size S (the
        smaller sibling — the larger one comes from parent-hist subtraction,
        the reference's FeatureHistogram::Subtract).  Padded slots hit the
        phantom zero row."""
        @phase("grow/hist")
        def branch(perm, start, cnt):
            seg = jax.lax.dynamic_slice(perm, (start,), (S,))
            valid = jnp.arange(S, dtype=jnp.int32) < cnt
            seg = jnp.where(valid, seg, n)
            return histogram_from_vals(
                bins_pad[seg], vals_pad[seg], num_bins=HB,
                impl=static.hist_impl,
                rows_block=min(cfg.rows_block, S),
                packed4=cfg.packed4, features=nf)
        return branch

    @phase("grow/select")
    def _apply_forced(st, scale3, meta, hist_of=None):
        """When the current step has a pending forced split (reference
        ForceSplits, serial_tree_learner.cpp:620), overwrite that leaf's
        stored best split with the forced (feature, bin) and its histogram-
        derived child stats; growth then proceeds through the normal split
        machinery.  Returns (state, forced_active, forced_index).
        ``hist_of(st, leaf)`` abstracts the histogram lookup — under the
        bounded pool it resolves the leaf's slot with recompute-on-miss
        (reference HistogramPool::Get miss semantics).  A missed forced
        leaf is recomputed here AND again as the split-time parent in the
        same step (the result is not threaded through the forced-stats
        cond); bounded at n_forced recomputes per tree, accepted for the
        simpler lockstep structure."""
        step = st.num_leaves - 1
        use = step < n_forced
        si = jnp.clip(step, 0, n_forced - 1)
        fleaf = st.forced_leaf[si]
        feat = F_FEAT[si]
        sbin = F_BIN[si]

        def _forced_stats(_):
            raw = (hist_of(st, fleaf) if hist_of is not None
                   else st.leaf_hist[fleaf])
            hist = _expand_hist(
                _scale_hist(raw, scale3), meta,
                st.leaf_sum_grad[fleaf], st.leaf_sum_hess[fleaf],
                st.leaf_count[fleaf])
            hb = hist[feat]                           # (B, 3)
            nanb = meta[1][feat]
            nan_pos = jnp.arange(hb.shape[0], dtype=jnp.int32) == nanb
            cum = jnp.cumsum(jnp.where(nan_pos[:, None], 0.0, hb), axis=0)
            pg, ph = st.leaf_sum_grad[fleaf], st.leaf_sum_hess[fleaf]

            def _gain(gl, hl):
                return (leaf_gain(gl, hl, cfg.split)
                        + leaf_gain(pg - gl, ph - hl, cfg.split)
                        - leaf_gain(pg, ph, cfg.split))

            # Both missing directions, as the normal split machinery does
            # (reference ForceSplits routes through ComputeBestSplitForFeature
            # so the missing direction is derived, not fixed).
            gl_r, hl_r, cl_r = cum[sbin, 0], cum[sbin, 1], cum[sbin, 2]
            gn = jnp.sum(jnp.where(nan_pos, hb[:, 0], 0.0))
            hn = jnp.sum(jnp.where(nan_pos, hb[:, 1], 0.0))
            cn = jnp.sum(jnp.where(nan_pos, hb[:, 2], 0.0))
            has_nan = nanb < hb.shape[0]
            dl = has_nan & (_gain(gl_r + gn, hl_r + hn) > _gain(gl_r, hl_r))
            gl = jnp.where(dl, gl_r + gn, gl_r)
            hl = jnp.where(dl, hl_r + hn, hl_r)
            cl = jnp.where(dl, cl_r + cn, cl_r)
            return gl, hl, cl, _gain(gl, hl), dl

        # Pay the expand+cumsum only while forced splits remain.
        gl, hl, cl, fgain, dleft = jax.lax.cond(
            use, _forced_stats,
            lambda _: (jnp.zeros((), jnp.float32),) * 4
            + (jnp.zeros((), bool),), None)
        tgt = jnp.where(use, fleaf, L + M)            # OOB drop when unused
        st = st._replace(
            best_gain=st.best_gain.at[tgt].set(fgain, mode="drop"),
            best_feature=st.best_feature.at[tgt].set(feat, mode="drop"),
            best_bin=st.best_bin.at[tgt].set(sbin, mode="drop"),
            best_default_left=st.best_default_left.at[tgt].set(
                dleft, mode="drop"),
            best_is_cat=st.best_is_cat.at[tgt].set(False, mode="drop"),
            best_cat_mask=st.best_cat_mask.at[tgt].set(
                jnp.zeros(B, bool), mode="drop"),
            best_gl=st.best_gl.at[tgt].set(gl, mode="drop"),
            best_hl=st.best_hl.at[tgt].set(hl, mode="drop"),
            best_cl=st.best_cl.at[tgt].set(cl, mode="drop"),
        )
        return st, use, si

    @phase("grow/update")
    def _record_forced_children(st, use, si, leaf, new_leaf):
        """Map the executed forced node's forced children onto the two
        result leaves."""
        lc = jnp.where(use & (F_LC[si] >= 0) & (F_LC[si] < n_forced),
                       F_LC[si], n_forced)
        rc = jnp.where(use & (F_RC[si] >= 0) & (F_RC[si] < n_forced),
                       F_RC[si], n_forced)
        return st._replace(
            forced_leaf=st.forced_leaf.at[lc].set(leaf, mode="drop")
                                      .at[rc].set(new_leaf, mode="drop"))

    def _root_best(state, scale3, meta, feature_mask, root_pen,
                   groups_mat=None, rs=None):
        """Root split search (shared by both layouts)."""
        key = None
        if need_key:
            rng, key = jax.random.split(state.rng)
            state = state._replace(rng=rng)
        root_hist_s = _expand_hist(
            _scale_hist(state.leaf_hist[0], scale3), meta,
            state.leaf_sum_grad[0], state.leaf_sum_hess[0],
            state.leaf_count[0], rs)
        bs = _best_for(root_hist_s,
                       state.leaf_sum_grad[0],
                       state.leaf_sum_hess[0], state.leaf_count[0], meta,
                       feature_mask, root_pen, state.leaf_out[0], key,
                       state.leaf_path[0], groups_mat,
                       state.leaf_lo[0] if cfg.split.has_monotone else None,
                       state.leaf_hi[0] if cfg.split.has_monotone else None,
                       state.leaf_depth[0], rs=rs)
        if rs is not None:
            # slice-local root scan -> globalize (SyncUpGlobalBestSplit)
            bs = rs["sync"](bs)
        return state, bs

    @phase("grow/setup")
    def _perm_setup(bins, vals, scale3, meta, feature_mask, cegb, key,
                    groups_mat=None, axis=None, rs=None, pool_slots=None,
                    voting=False):
        """Shared permutation-layout prologue: padded arrays, buckets, root
        histogram/state/best-split.  ``axis`` = shard_map axis name for the
        cross-shard histogram reduction (None = single device); ``rs`` = the
        reduce-scatter context (then leaf_hist holds only the owned feature
        block)."""
        n, gcols = bins.shape
        nfeat = meta[0].shape[0]
        bins_pad = jnp.concatenate([bins, jnp.zeros((1, gcols), bins.dtype)],
                                   0)
        vals_pad = jnp.concatenate([vals, jnp.zeros((1, 3), vals.dtype)], 0)
        buckets = _split_buckets(n)
        max_bucket = buckets[-1]
        buckets_arr = jnp.asarray(buckets, jnp.int32)
        perm0 = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                                 jnp.full(max_bucket, n, jnp.int32)])
        root_hist = histogram_from_vals(
            bins, vals, num_bins=HB, impl=static.hist_impl,
            packed4=cfg.packed4, features=meta[0].shape[0],
            rows_block=cfg.rows_block)
        if axis is not None and not voting:
            # The reference's histogram reduce
            # (data_parallel_tree_learner.cpp:284) — integer tensors under
            # quantized training (bin.h:48-81).  Voting mode keeps leaf
            # histograms LOCAL and reduces only vote winners;
            # reduce-scatter mode keeps only the owned feature block.
            root_hist = (rs["scatter"](root_hist) if rs is not None
                         else _psum(root_hist, axis))
        if rs is not None:
            # Every feature's bins sum to the leaf totals; the owner of
            # histogram column 0 (shard 0) computes them from its reduced
            # block and the one-hot psum broadcast delivers the bitwise
            # value the allreduce path would see.
            tot0 = jnp.sum(_scale_hist(root_hist[0:1], scale3)[0], axis=0)
            mine0 = jax.lax.axis_index(axis) == 0
            root_tot = _psum(
                jnp.where(mine0, tot0, jnp.zeros_like(tot0)), axis)
        else:
            root_tot = jnp.sum(_scale_hist(root_hist[0:1], scale3)[0],
                               axis=0)
            if voting:
                root_tot = _psum(root_tot, axis)
        root_g, root_h, root_c = root_tot[0], root_tot[1], root_tot[2]
        # leaf_hist columns live in HISTOGRAM feature space, which under
        # packed4 is the unpacked F (bins columns are nibble pairs) and
        # under reduce-scatter is the owned block width
        hist_cols = nfeat if cfg.packed4 else gcols
        if rs is not None:
            hist_cols = rs["go"]
        state = _init_state(n, nfeat, hist_cols, root_hist, root_g, root_h,
                            root_c, key, pool_slots)
        state = state._replace(perm=perm0)
        root_pen = None
        if cfg.split.use_cegb and cegb is not None:
            root_pen = _cegb_penalty(root_c, state.feat_used,
                                     state.leaf_path[0], *cegb)
        if voting:
            vkey = None
            if need_key:
                rng, vkey = jax.random.split(state.rng)
                state = state._replace(rng=rng)
            bs1 = _vote_best_batch(
                state.leaf_hist[0:1], root_g[None], root_h[None],
                root_c[None], state.leaf_out[0:1], scale3, meta,
                feature_mask, None, None, axis,
                penaltyk=None if root_pen is None else root_pen[None],
                key=vkey,
                pathk=state.leaf_path[0:1] if track_path else None,
                groups_mat=groups_mat)
            root_bs = jax.tree.map(lambda a: a[0], bs1)
        else:
            state, root_bs = _root_best(state, scale3, meta, feature_mask,
                                        root_pen, groups_mat, rs)
        state = _store_best(state, jnp.asarray(0), root_bs, jnp.asarray(True))
        return state, bins_pad, vals_pad, buckets, buckets_arr, max_bucket

    @phase("grow/finish")
    def _row_leaf_from_perm(state, n, max_bucket):
        """row -> leaf assignment from the final grouped permutation
        (``_row_leaf_map``); ``n + max_bucket`` is past ``perm``'s end."""
        return _row_leaf_map(state.leaf_start, state.leaf_rows,
                             state.num_leaves, state.perm, n, n + max_bucket)

    # ------------------------------------------------------------------ wave path
    def _grow_wave(bins, vals, scale3, feature_mask, meta, plan, cegb=None,
                   key=None, axis=None, faxis=None, rows=None):
        """Wave growth (permutation layout): split the top-W leaves per step.
        THE permutation-layout body: single device, per shard under
        ``shard_map`` when ``axis`` names the mesh data axis, or
        feature-sharded when ``faxis`` names the feature axis (rows
        replicated, each shard histograms and scans only its own columns —
        the reference FeatureParallelTreeLearner layout).  A wave of one
        (``leaf_batch=1``) is the reference's sequential leaf-wise order,
        and the only wave forced splits and ``faxis`` run in.

        Per wave: partition the chosen leaves' contiguous segments in one
        ragged pass (``_partition_wave``), histogram
        each SMALLER sibling's contiguous range, get the larger siblings by
        subtraction, and search all 2W children's splits.  Either way
        the W segments are packed back to back and handed to ONE ragged
        kernel launch, padded only to the next step of a total-row ladder:
        fused (``_fused_wave``) the launch also subtracts and scans;
        unfused (``_ragged_wave``) it is ``histogram_ragged`` — once per
        column chunk where a histogram is wider than one launch — and an
        XLA subtract and one vmapped scan follow.  The cost is the rows
        handed over — the kernels take 6 ns a row at 28 columns and 25 at
        137 on a v5e, 0.18-0.21 ns a row-column, instruction-bound and
        nowhere near the HBM stream's rate (my chip runs, PR 28; PERF.md
        section 5) — so no Pallas path pads a wave beyond the rows it
        holds.  (The per-leaf call at a power-of-two bucket,
        ``_hist_branch_for``, stays for the root's rows, the pool's
        recompute-on-miss, forced splits, and per slot for the ``onehot``
        / ``segment`` implementations, which have no ragged form.)
        Sequential depth
        per tree drops from num_leaves-1 steps to
        ~ceil((num_leaves-1)/W).

        ``rows`` (single device only) = the in-bag row ids of a sampled
        tree, ``plan.sampling == "subset"``.  The sample's bins and values
        are copied out ONCE (the reference's ``Dataset::CopySubrow``) and
        the tree is grown on that copy as on a data set of its own: every
        pass above is sized by the in-bag count and reads rows that lie
        side by side (a gather by sparse row id reads 13-20 ns a row on a
        v5e where one by dense position reads 7; PERF.md, Findings PR 33).
        Every row's leaf — out-of-bag rows included — comes from
        ``_route_rows`` wave by wave, off the wave's splits applied to the
        feature-major copy of ALL rows, instead of from the final
        ``perm``."""
        f = meta[0].shape[0]
        route = rows is not None
        if route:
            assert axis is None and faxis is None
            with phase("grow/setup"):
                # a slot no row filled holds bins.shape[0]: a zero row
                bins_fm_all = jnp.pad(bins, ((0, 1), (0, 0))).T
                bins = jnp.take(bins, rows, axis=0, mode="fill",
                                fill_value=0)
                vals = jnp.take(vals, rows, axis=0, mode="fill",
                                fill_value=0)
        n, gcols = bins.shape
        W = min(cfg.leaf_batch, max(L - 1, 1))
        assert W == 1 or not (n_forced or faxis), (W, n_forced, faxis)
        voting = plan.reduce == "vote"
        nan_bins = meta[1]
        groups_mat = _groups_matrix(f) if use_groups else None
        rs = None
        hist_cols = f if cfg.packed4 else gcols
        if plan.reduce == "scatter":
            rs = _make_rs(axis, hist_cols, meta)
        # slice-local scans (owned feature block / own feature columns)
        # globalize their winners as one SplitInfo payload
        sync = rs["sync"] if rs is not None else None
        fp_mono = None
        if faxis is not None:
            foffset = jax.lax.axis_index(faxis) * f
            sync = phase("grow/reduce")(
                lambda bs: _fp_sync_best(bs, foffset, faxis, fp_shards))

            def fp_mono(feat_g):
                # constraint type of GLOBAL features: the owner shard
                # broadcasts it (the local meta holds only owned features)
                lf = feat_g - foffset
                owns = (lf >= 0) & (lf < f)
                m = jnp.where(owns, meta[3][jnp.clip(lf, 0, f - 1)], 0)
                return _psum(m, faxis)
        P, pool_on, pool_claim, pool_assign, _reduce_hist = _pool_setup(
            rs["go"] if rs is not None else hist_cols, axis, rs)
        (state, bins_pad, vals_pad, buckets, buckets_arr,
         max_bucket) = _perm_setup(bins, vals, scale3, meta, feature_mask,
                                   cegb, key, groups_mat, axis, rs, P,
                                   voting)
        if faxis is not None:
            # _perm_setup stored the LOCAL root best; globalize it
            # (reference SyncUpGlobalBestSplit after the root scan).
            zero = jnp.zeros((), jnp.float32)
            bs0 = BestSplit(
                gain=state.best_gain[0], feature=state.best_feature[0],
                bin=state.best_bin[0],
                default_left=state.best_default_left[0],
                is_cat=state.best_is_cat[0],
                cat_mask=state.best_cat_mask[0],
                sum_grad_left=state.best_gl[0],
                sum_hess_left=state.best_hl[0],
                count_left=state.best_cl[0],
                sum_grad_right=zero, sum_hess_right=zero, count_right=zero)
            state = _store_best(state, jnp.asarray(0), sync(bs0),
                                jnp.asarray(True))

        if faxis is None:
            # a split's column is one contiguous row of the feature-major
            # bins (a dense transpose, once a tree): _go_left_bits
            with phase("grow/setup"):
                bins_fm = bins_pad.T
        hist_branches = [_hist_branch_for(bins_pad, vals_pad, n, S,
                                          meta[0].shape[0])
                         for S in buckets]

        def _bucket_of(cnt, sizes=buckets_arr):
            return _ladder_step(sizes, cnt)

        def _pool_hist(st, sl, miss, start, cnt):
            """Pool lookup with recompute-on-miss (reference
            HistogramPool::Get returning false -> the learner reconstructs
            the leaf's histogram from its rows): an evicted leaf's
            histogram is rebuilt from its contiguous perm segment — whose
            row order is untouched since the leaf was created, so a leaf
            originally histogrammed directly recomputes bit-identically —
            and re-reduced across shards exactly like the resident path."""
            def rec(_):
                h = jax.lax.switch(_bucket_of(cnt), hist_branches, st.perm,
                                   start, cnt)
                return _reduce_hist(h)

            return jax.lax.cond(
                miss, rec,
                lambda _: st.leaf_hist[jnp.clip(sl, 0, P - 1)], None)

        # ---- fused wave kernel (ops/pallas_wave.py): the plan's word, a
        # trace-time static
        use_fused = plan.fused
        if use_fused:
            from ..ops.pallas_common import C_PAD, interpret_mode
            from ..ops.pallas_wave import (fused_wave_call, hist_from_flat,
                                           hist_to_flat, payload_to_best,
                                           plane_order, wave_block_map,
                                           wave_block_slots, wave_dtype_for,
                                           wave_layout, wave_meta)
            wave_dtype = wave_dtype_for(cfg)
            _lay = wave_layout(f, HB, wave_dtype, cfg.rows_block,
                               cfg.packed4)
            _w_order, _w_inv = plane_order(f, cfg.packed4)
            wave_meta_w = wave_meta(meta[0], meta[1], meta[2], feature_mask,
                                    features=f, num_bins=HB,
                                    packed4=cfg.packed4)
            wave_scale = (None if scale3 is None
                          else jnp.pad(scale3, (0, 1))
                          .reshape(1, 4).astype(jnp.float32))

            blk = _lay["rows_block"]
            # smaller siblings of disjoint leaves hold at most half the
            # rows, and each of the W slots rounds up to whole blocks
            wave_totals = _wave_row_ladder(W * blk,
                                           (n // (2 * blk) + W) * blk, blk)
            wave_totals_arr = jnp.asarray(wave_totals, jnp.int32)

            def _fused_wave(perm, small_start, small_cnt, small_left,
                            parent_hist, g2c, h2c, c2c, o2c, active):
                """ONE pallas dispatch for the whole wave, handed the rows
                the wave has: the W smaller siblings' contiguous perm
                segments are packed back to back in whole row blocks
                (``wave_block_map``; rows past a slot's count hit the
                phantom zero row), padded only to the next step of the
                TOTAL-row ladder, and a block -> slot map tells the kernel
                whose histogram each block belongs to.  Build + subtract +
                scan in VMEM; returns ``(hist_left, hist_right, bs)`` with
                the 2W-child BestSplit batch in the unfused path's cat2
                ordering."""
                with phase("grow/wave_unpack"):
                    parent_flat = hist_to_flat(parent_hist, _lay["ftile"],
                                               _lay["b_pad"], _w_order)
                    sl2 = jnp.broadcast_to(
                        small_left.astype(jnp.float32)[:, None], g2c.shape)
                    act2 = jnp.broadcast_to(
                        active.astype(jnp.float32)[:, None], g2c.shape)
                    z2 = jnp.zeros_like(g2c)
                    stats = jnp.stack(
                        [g2c, h2c, c2c, o2c, sl2, act2, z2, z2],
                        axis=-1)                             # (W, 2, 8)

                with phase("grow/select"):
                    _, off, nb_total = wave_block_map(small_cnt, blk)
                    ti = _bucket_of(nb_total * blk, wave_totals_arr)

                def branch_for(T):
                    # the gather that feeds the kernel, and the kernel
                    # launch, whose path ends in the rows it is handed
                    @phase("grow/wave_gather")
                    def br(_):
                        slot, k = wave_block_slots(off, T // blk)
                        row0 = k * blk
                        seg = jax.vmap(
                            lambda s0: jax.lax.dynamic_slice(
                                perm, (s0,), (blk,)))(
                                    small_start[slot] + row0)
                        valid = (row0[:, None]
                                 + jnp.arange(blk, dtype=jnp.int32)[None, :]
                                 < small_cnt[slot][:, None])
                        seg = jnp.where(valid, seg, n).reshape(T)
                        gbins = bins_pad[seg]                # (T, ct)
                        gvT = jnp.pad(vals_pad[seg],
                                      ((0, 0), (0, C_PAD - 3))).T
                        with kernel_rows(T, _lay["ftile"]):
                            return fused_wave_call(
                                gbins, gvT, parent_flat, stats, wave_meta_w,
                                slot, nb_total[None], wave_scale,
                                num_bins=HB, features=f,
                                rows_block=cfg.rows_block,
                                dtype=wave_dtype, packed4=cfg.packed4,
                                scfg=cfg.split, interpret=interpret_mode())
                    return br

                hist2, payload = jax.lax.switch(
                    ti, [branch_for(T) for T in wave_totals], 0)
                with phase("grow/wave_unpack"):
                    child = hist_from_flat(hist2, f, HB, _lay["b_pad"],
                                           _w_inv)           # (W,2,F,HB,3)
                    bs = payload_to_best(jnp.concatenate(
                        [payload[:, 0], payload[:, 1]], axis=0))
                return child[:, 0], child[:, 1], bs

        # ---- the unfused wave's histograms (ops/pallas_histogram.py):
        # the same packing as the fused wave's, without subtract and scan
        elif plan.hist_impl == "pallas":
            from ..ops.pallas_common import interpret_mode
            from ..ops.pallas_histogram import (histogram_ragged,
                                                kernel_layout,
                                                ragged_block_map)
            from ..ops.pallas_wave import wave_block_map, wave_dtype_for
            hist_dtype = wave_dtype_for(cfg)
            blk, hist_ftile = kernel_layout(hist_cols, HB, hist_dtype,
                                            cfg.rows_block, cfg.packed4)[:2]
            # the segments are cut in granules (a multiple of the kernel's
            # block) and the kernel skips the padding blocks INSIDE a
            # granule as it skips the ladder's: it pays for the rows a
            # slot holds rounded to ``blk``, the gather for them rounded
            # to ``gran`` and to the ladder step
            gran = max(_WAVE_GRANULE, blk)
            # smaller siblings of disjoint leaves hold at most half the
            # rows; on a data shard the GLOBAL smaller side may hold all of
            # them; each of the W slots rounds up to whole granules
            most = n if axis is not None else n // 2
            wave_totals = _ragged_wave_totals(most, W, gran)
            wave_totals_arr = jnp.asarray(wave_totals, jnp.int32)

            def _ragged_wave(perm, small_start, small_cnt):
                """The W smaller siblings' RAW histograms ``(W, G, HB, 3)``
                from ONE gather and ONE kernel launch (a column chunk):
                their contiguous perm segments packed back to back in whole
                granules (``wave_block_map``; rows past a slot's count hit
                the phantom zero row), padded only to the next step of the
                TOTAL-row ladder, and a block -> slot map that tells the
                kernel whose histogram each row block belongs to and which
                blocks hold no row at all."""
                with phase("grow/select"):
                    _, goff, ng_total = wave_block_map(small_cnt, gran)
                    ti = _bucket_of(ng_total * gran, wave_totals_arr)

                def branch_for(T):
                    @phase("grow/hist")
                    def br(_):
                        # granule -> (slot, place in it, its slot's start
                        # and count): W compares a granule and ONE lookup,
                        # which is all the index work a branch compiles
                        g = jnp.arange(T // gran, dtype=jnp.int32)
                        gslot = jnp.sum(g[:, None] >= goff[None, :],
                                        axis=1, dtype=jnp.int32) - 1
                        at = jnp.stack([goff, small_start, small_cnt],
                                       axis=1)[gslot]
                        gk, gcnt = g - at[:, 0], at[:, 2]
                        row0 = gk * gran
                        seg = jax.vmap(
                            lambda s0: jax.lax.dynamic_slice(
                                perm, (s0,), (gran,)))(at[:, 1] + row0)
                        valid = (row0[:, None]
                                 + jnp.arange(gran, dtype=jnp.int32)[None, :]
                                 < gcnt[:, None])
                        seg = jnp.where(valid, seg, n).reshape(T)
                        blocks = ragged_block_map(gslot, gk, gcnt, blk, gran)
                        gbins, gvals = bins_pad[seg], vals_pad[seg]
                        with kernel_rows(T, hist_ftile,
                                         -(-hist_cols // hist_ftile)):
                            return histogram_ragged(
                                gbins, gvals, blocks, slots=W, num_bins=HB,
                                rows_block=cfg.rows_block, dtype=hist_dtype,
                                packed4=cfg.packed4, features=f,
                                interpret=interpret_mode())
                    return br

                return jax.lax.switch(
                    ti, [branch_for(T) for T in wave_totals], 0)

        if use_fused or plan.hist_impl == "pallas":
            # what the traced shape selects, for the registry (set once a
            # compile): rows per packing granule, instances of the ladder
            registry().gauge("hist.wave_granule").set(
                blk if use_fused else gran)
            registry().gauge("hist.wave_ladder_steps").set(len(wave_totals))
        # the bytes the wave's row reads move as slices (_take_rows), set
        # once a compile: the W parents' carry rows (the pool reads its
        # own), the W split columns of the feature-major bins, and of all
        # rows' where a sampled tree routes its out-of-bag rows; 0 where
        # every read is a gather
        registry().gauge("grow.wave_read_bytes").set(sum(
            _take_rows_bytes(a, W) for a in
            ([] if pool_on else [state.leaf_hist])
            + ([] if faxis is not None else [bins_fm])
            + ([bins_fm_all] if route else [])))

        def step(st: _GrowState, row_leaf=None):
            """One wave; ``row_leaf`` (a sampled tree only) is every row's
            leaf so far, and is returned beside the state."""
            if n_forced:
                st, use_f, si = _apply_forced(
                    st, scale3, meta,
                    hist_of=(lambda s, l: _pool_hist(
                        s, s.leaf_slot[l], s.leaf_slot[l] < 0,
                        s.leaf_start[l], s.leaf_rows[l]))
                    if pool_on else None)
            with phase("grow/select"):
                budget = L - st.num_leaves
                top_g, top_l = jax.lax.top_k(st.best_gain, W)
                slot = jnp.arange(W, dtype=jnp.int32)
                active = (top_g > _NEG_INF) & (slot < budget)
                if n_forced:
                    # a pending forced split IS this wave of one
                    top_l = jnp.where(use_f, st.forced_leaf[si],
                                      top_l).astype(jnp.int32)
                    top_g = st.best_gain[top_l]
                    active = active | use_f
                if inter:
                    # Conflict-free wave (per-wave bound recomputation): two
                    # leaves ORDERED by a monotone relation must not split in
                    # the same wave — each one's pre-wave bound assumes the
                    # other's output stays put for the wave.  Greedily keep
                    # candidates in gain order that are unordered w.r.t. every
                    # kept candidate; skipped leaves stay pending, so the
                    # executed split sequence remains best-first.
                    pu = _pair_up(st, meta[3])
                    rel = pu | pu.T
                    cand_rel = rel[top_l][:, top_l]                # (W, W)
                    wslot = jnp.arange(W)

                    def _sel(j, keep):
                        clash = jnp.any(keep & (wslot < j) & cand_rel[j])
                        return keep.at[j].set(keep[j] & ~clash)

                    keep = jax.lax.fori_loop(0, W, _sel, jnp.ones(W, bool))
                    active = active & keep
                n_act = jnp.sum(active.astype(jnp.int32))
                rank = (jnp.cumsum(active.astype(jnp.int32))
                        - active.astype(jnp.int32))
                # Inactive slots scatter out-of-bounds (dropped by XLA).
                node_j = jnp.where(active, st.num_leaves - 1 + rank, M + L)
                newleaf_j = jnp.where(active, st.num_leaves + rank, L + M)
                leaf_j = jnp.where(active, top_l, L + M)

                starts = st.leaf_start[top_l]
                cnts = jnp.where(active, st.leaf_rows[top_l], 0)
                feats = st.best_feature[top_l]
                sbins = st.best_bin[top_l]
                dlefts = st.best_default_left[top_l]
                scats = st.best_is_cat[top_l]
                cmasks = st.best_cat_mask[top_l]
                raw_dtype = jnp.int32 if cfg.quantized else jnp.float32

            if pool_on:
                with phase("grow/hist"):
                    # W parent histograms BEFORE the partition reorders their
                    # segments: resident slots, or recompute-on-miss from the
                    # leaf's rows in creation-time order (reference
                    # HistogramPool::Get miss -> reconstruct), re-reduced
                    # across shards exactly like the smaller-sibling path.
                    spW = st.leaf_slot[top_l]                       # (W,)
                    missW = active & (spW < 0)

                    def parent_one(j, ph):
                        return ph.at[j].set(_pool_hist(
                            st, spW[j], missW[j], starts[j], cnts[j]))

                    parent_hist = jax.lax.fori_loop(
                        0, W, parent_one,
                        jnp.zeros((W,) + st.leaf_hist.shape[1:], raw_dtype))

            with phase("grow/partition"):
                if faxis is not None:
                    # the split column lives on one shard: its owner
                    # broadcasts the go-left vector, read by row id
                    glv = _fp_go_left(
                        bins_pad, nan_bins, feats[0], sbins[0], dlefts[0],
                        scats[0], cmasks[0], foffset, f, faxis)
                    go_left = glv.astype(jnp.int32)
                else:
                    go_left = _go_left_bits(cfg, bins_fm, meta, feats, sbins,
                                            dlefts, scats, cmasks)
                perm, nl_phys = _partition_wave(st.perm, starts, cnts,
                                                go_left, n)
                if route:
                    with segment("oob_route"):
                        row_leaf = _route_rows(
                            row_leaf, _go_left_bits(
                                cfg, bins_fm_all, meta, feats, sbins, dlefts,
                                scats, cmasks), leaf_j, newleaf_j)

            with phase("grow/select"):
                if axis is None:
                    small_left = nl_phys <= cnts - nl_phys
                else:
                    # Global small/large choice so every shard histograms the
                    # same side (reference data-parallel smaller-leaf sync,
                    # data_parallel_tree_learner.cpp:224).
                    nl_g = _psum(nl_phys, axis)
                    cnt_g = _psum(cnts, axis)
                    small_left = nl_g <= cnt_g - nl_g
                small_start = jnp.where(small_left, starts, starts + nl_phys)
                small_cnt = jnp.where(small_left, nl_phys, cnts - nl_phys)

                pg = st.leaf_sum_grad[top_l]
                ph = st.leaf_sum_hess[top_l]
                pc = st.leaf_count[top_l]
                gl, hl, cl = (st.best_gl[top_l], st.best_hl[top_l],
                              st.best_cl[top_l])
                gr, hr, cr = pg - gl, ph - hl, pc - cl
                pout = st.leaf_out[top_l]
                out_l = smoothed_output(gl, hl, cl, pout, cfg.split)
                out_r = smoothed_output(gr, hr, cr, pout, cfg.split)

                if not pool_on:
                    parent_hist = _take_rows(st.leaf_hist, top_l)

            fused_bs = None
            if use_fused:
                # ONE fused pallas dispatch for the whole wave (ISSUE-7):
                # histogram build + sibling subtract + split scan while
                # the (C_PAD, F*B) accumulators stay VMEM-resident.  The
                # monotone/voting/CEGB branches below are statically off
                # on this path (the plan refuses to fuse them).
                hist_left, hist_right, fused_bs = _fused_wave(
                    perm, small_start, small_cnt, small_left, parent_hist,
                    jnp.stack([gl, gr], 1), jnp.stack([hl, hr], 1),
                    jnp.stack([cl, cr], 1), jnp.stack([out_l, out_r], 1),
                    active)
            else:
                if plan.hist_impl == "pallas":
                    hist_small = _ragged_wave(perm, small_start, small_cnt)
                else:
                    # onehot / segment have no ragged form: a slot at a
                    # time, at its own bucket
                    def hist_one(j, hs):
                        h = jax.lax.switch(
                            _bucket_of(small_cnt[j]), hist_branches, perm,
                            small_start[j], small_cnt[j])
                        return hs.at[j].set(h)

                    with phase("grow/hist"):
                        hist_small = jax.lax.fori_loop(
                            0, W, hist_one,
                            jnp.zeros((W, hist_cols, HB, 3), raw_dtype))
                if axis is not None and not voting:
                    # ONE cross-shard reduce per wave — integer tensors
                    # under quantized training (bin.h:48-81; int16 on the
                    # wire when the reduce-scatter overflow guard allows).
                    # Voting mode reduces only the vote winners' slices
                    # (_vote_best_batch); reduce-scatter mode leaves each
                    # shard its owned feature block (the reference's
                    # ReduceScatter, data_parallel_tree_learner.cpp:284).
                    hist_small = (rs["scatter"](hist_small)
                                  if rs is not None
                                  else _psum(hist_small, axis))

                with phase("grow/subtract"):
                    hist_big = parent_hist - hist_small
                    sl = small_left[:, None, None, None]
                    hist_left = jnp.where(sl, hist_small, hist_big)
                    hist_right = jnp.where(sl, hist_big, hist_small)

            with phase("grow/update"):
                bounds2 = None
                if cfg.split.has_monotone and inter:
                    # Intermediate/advanced: clip to the pre-wave refreshed
                    # bounds (per-threshold slices when advanced); children
                    # inherit the parent bounds verbatim and the REAL bounds
                    # come from the post-wave refresh.  Track child bin
                    # rectangles for the adjacency pass.
                    plo, phi = st.leaf_lo[top_l], st.leaf_hi[top_l]
                    if adv:
                        out_l = jnp.clip(out_l, st.adv_llo[top_l],
                                         st.adv_lhi[top_l])
                        out_r = jnp.clip(out_r, st.adv_rlo[top_l],
                                         st.adv_rhi[top_l])
                    else:
                        out_l = jnp.clip(out_l, plo, phi)
                        out_r = jnp.clip(out_r, plo, phi)
                    cut = (sbins + 1)[:, None]
                    lo_p = st.leaf_bin_lo[top_l]                   # (W, F)
                    hi_p = st.leaf_bin_hi[top_l]
                    fhot1 = (jnp.arange(lo_p.shape[1])[None, :]
                             == feats[:, None])
                    isnum = (~scats)[:, None]
                    hi_l_r = jnp.where(fhot1 & isnum,
                                       jnp.minimum(hi_p, cut), hi_p)
                    lo_r_r = jnp.where(fhot1 & isnum,
                                       jnp.maximum(lo_p, cut), lo_p)
                    pair_idx = jnp.concatenate([leaf_j, newleaf_j])
                    st = st._replace(
                        leaf_bin_lo=st.leaf_bin_lo.at[pair_idx].set(
                            jnp.concatenate([lo_p, lo_r_r]), mode="drop"),
                        leaf_bin_hi=st.leaf_bin_hi.at[pair_idx].set(
                            jnp.concatenate([hi_l_r, hi_p]), mode="drop"),
                        leaf_lo=st.leaf_lo.at[pair_idx].set(
                            jnp.concatenate([plo, plo]), mode="drop"),
                        leaf_hi=st.leaf_hi.at[pair_idx].set(
                            jnp.concatenate([phi, phi]), mode="drop"))
                    # bounds2 stays None: the children best-split pass is
                    # skipped on this path (the per-wave refresh recomputes
                    # every leaf's split against fresh bounds)
                elif cfg.split.has_monotone:
                    plo, phi = st.leaf_lo[top_l], st.leaf_hi[top_l]
                    out_l = jnp.clip(out_l, plo, phi)
                    out_r = jnp.clip(out_r, plo, phi)
                    mono_t = (meta[3][feats] if fp_mono is None
                              else fp_mono(feats))
                    is_num = ~scats
                    mid = (out_l + out_r) / 2.0
                    lo_l = jnp.where((mono_t < 0) & is_num,
                                     jnp.maximum(plo, mid), plo)
                    hi_l = jnp.where((mono_t > 0) & is_num,
                                     jnp.minimum(phi, mid), phi)
                    lo_r = jnp.where((mono_t > 0) & is_num,
                                     jnp.maximum(plo, mid), plo)
                    hi_r = jnp.where((mono_t < 0) & is_num,
                                     jnp.minimum(phi, mid), phi)
                    st = st._replace(
                        leaf_lo=st.leaf_lo.at[
                            jnp.concatenate([leaf_j, newleaf_j])].set(
                            jnp.concatenate([lo_l, lo_r]), mode="drop"),
                        leaf_hi=st.leaf_hi.at[
                            jnp.concatenate([leaf_j, newleaf_j])].set(
                            jnp.concatenate([hi_l, hi_r]), mode="drop"))
                    bounds2 = (jnp.concatenate([lo_l, lo_r]),
                               jnp.concatenate([hi_l, hi_r]))

                # ---- tree updates (batched scatters over W nodes)
                tr = st.tree
                parent = st.leaf_parent[top_l]
                was_left = st.leaf_is_left[top_l]
                pl_idx = jnp.where(active & (parent >= 0) & was_left,
                                   jnp.maximum(parent, 0), M + L)
                pr_idx = jnp.where(active & (parent >= 0) & ~was_left,
                                   jnp.maximum(parent, 0), M + L)
                left_child = tr.left_child.at[pl_idx].set(node_j, mode="drop")
                right_child = tr.right_child.at[pr_idx].set(
                    node_j, mode="drop")
                tree = tr._replace(
                    split_feature=tr.split_feature.at[node_j].set(
                        feats, mode="drop"),
                    split_bin=tr.split_bin.at[node_j].set(sbins, mode="drop"),
                    default_left=tr.default_left.at[node_j].set(
                        dlefts, mode="drop"),
                    is_cat=tr.is_cat.at[node_j].set(scats, mode="drop"),
                    cat_mask=tr.cat_mask.at[node_j].set(cmasks, mode="drop"),
                    left_child=left_child.at[node_j].set(~leaf_j, mode="drop"),
                    right_child=right_child.at[node_j].set(
                        ~newleaf_j, mode="drop"),
                    split_gain=tr.split_gain.at[node_j].set(
                        top_g, mode="drop"),
                    internal_value=tr.internal_value.at[node_j].set(
                        pout, mode="drop"),
                    internal_count=tr.internal_count.at[node_j].set(
                        pc, mode="drop"),
                )

                # ---- per-leaf state (batched scatters over 2W children)
                idx2 = jnp.concatenate([leaf_j, newleaf_j])
                cat2 = lambda a, b: jnp.concatenate([a, b])
                depth = st.leaf_depth[top_l] + 1
                hist_idx2 = idx2
                if pool_on:
                    # Claim W smaller-sibling slots (+ replacements for missed
                    # parents); larger siblings take over their parents' slots.
                    st, ssW, sbW = pool_claim(st, spW, active, missW)
                    slot_l = jnp.where(small_left, ssW, sbW)
                    slot_r = jnp.where(small_left, sbW, ssW)
                    hist_idx2 = cat2(slot_l, slot_r)
                    st = pool_assign(st, idx2, hist_idx2)
                st = st._replace(
                    perm=perm,
                    tree=tree,
                    num_leaves=st.num_leaves + n_act,
                    leaf_start=st.leaf_start.at[newleaf_j].set(
                        starts + nl_phys, mode="drop"),
                    leaf_rows=st.leaf_rows.at[leaf_j].set(nl_phys, mode="drop")
                                         .at[newleaf_j].set(cnts - nl_phys,
                                                            mode="drop"),
                    leaf_hist=st.leaf_hist.at[hist_idx2].set(
                        cat2(hist_left, hist_right), mode="drop"),
                    leaf_sum_grad=st.leaf_sum_grad.at[idx2].set(
                        cat2(gl, gr), mode="drop"),
                    leaf_sum_hess=st.leaf_sum_hess.at[idx2].set(
                        cat2(hl, hr), mode="drop"),
                    leaf_count=st.leaf_count.at[idx2].set(
                        cat2(cl, cr), mode="drop"),
                    leaf_depth=st.leaf_depth.at[idx2].set(
                        cat2(depth, depth), mode="drop"),
                    leaf_parent=st.leaf_parent.at[idx2].set(
                        cat2(node_j, node_j), mode="drop"),
                    leaf_is_left=st.leaf_is_left.at[idx2].set(
                        cat2(jnp.ones(W, bool), jnp.zeros(W, bool)),
                        mode="drop"),
                    leaf_out=st.leaf_out.at[idx2].set(
                        cat2(out_l, out_r), mode="drop"),
                )

                # ---- path tracking (CEGB / interaction constraints)
                penalty2 = None
                path2 = None
                if track_path:
                    fhot = (jnp.arange(f)[None, :] == feats[:, None]) \
                        & active[:, None]                        # (W, F)
                    child_path = st.leaf_path[top_l] | fhot      # (W, F)
                    path2 = cat2(child_path, child_path)
                    st = st._replace(
                        leaf_path=st.leaf_path.at[idx2].set(
                            path2, mode="drop"))
                if cfg.split.use_cegb and cegb is not None:
                    coupled, lazy = cegb
                    feat_used = st.feat_used | jnp.any(fhot, axis=0)
                    st = st._replace(feat_used=feat_used)
                    if not inter:
                        # the inter path's refresh recomputes penaltyL for all
                        # leaves; computing the per-child pair here would be
                        # dead work in the jitted hot loop
                        pen_l = jax.vmap(
                            lambda c, p: _cegb_penalty(
                                c, feat_used, p, coupled, lazy))(
                                    cl, child_path)
                        pen_r = jax.vmap(
                            lambda c, p: _cegb_penalty(
                                c, feat_used, p, coupled, lazy))(
                                    cr, child_path)
                        penalty2 = cat2(pen_l, pen_r)

            if n_forced:
                st = _record_forced_children(st, use_f, si, top_l[0],
                                             newleaf_j[0])
            if inter:
                # Per-wave bound + best-split refresh over ALL leaves.  The
                # 2W children's searches are part of the full rescan, so
                # the dedicated children pass below is skipped.  Safe with
                # forced splits: this overwrites best_* for ALL leaves, but
                # _apply_forced re-pins the pending forced directive at
                # the START of the next step, so a forced split is never
                # lost (test_forced_splits_survive_intermediate_monotone).
                return _inter_refresh(st, scale3, meta, feature_mask, cegb,
                                      groups_mat), row_leaf

            # ---- best splits for all 2W children in one vmapped search
            # (already computed IN the kernel on the fused path)
            with phase("grow/scan"):
                node_key = None
                if need_key:
                    rng, node_key = jax.random.split(st.rng)
                    st = st._replace(rng=rng)
            if use_fused:
                bs = fused_bs
            elif voting:
                bs = _vote_best_batch(
                    cat2(hist_left, hist_right), cat2(gl, gr),
                    cat2(hl, hr), cat2(cl, cr), cat2(out_l, out_r), scale3,
                    meta, feature_mask, bounds2, cat2(depth, depth), axis,
                    penaltyk=penalty2, key=node_key, pathk=path2,
                    groups_mat=groups_mat)
            else:
                with phase("grow/scan"):
                    hist2s = _expand_hist_batch(
                        _scale_hist(cat2(hist_left, hist_right), scale3), meta,
                        cat2(gl, gr), cat2(hl, hr), cat2(cl, cr), rs)
                bs = _best_for_batch(hist2s, cat2(gl, gr), cat2(hl, hr),
                                     cat2(cl, cr), meta, feature_mask,
                                     penalty2, cat2(out_l, out_r), node_key,
                                     path2, groups_mat, bounds2,
                                     cat2(depth, depth), rs=rs)
                if sync is not None:
                    # All 2W slice-local winners globalize in one vmapped
                    # payload broadcast (SyncUpGlobalBestSplit).
                    bs = sync(bs)
            with phase("grow/update"):
                if cfg.max_depth <= 0:
                    depth_ok = jnp.ones(2 * W, bool)
                else:
                    depth_ok = cat2(depth, depth) < cfg.max_depth
                gain2 = jnp.where(depth_ok, bs.gain, _NEG_INF)
                return st._replace(
                    best_gain=st.best_gain.at[idx2].set(gain2, mode="drop"),
                    best_feature=st.best_feature.at[idx2].set(
                        bs.feature, mode="drop"),
                    best_bin=st.best_bin.at[idx2].set(bs.bin, mode="drop"),
                    best_default_left=st.best_default_left.at[idx2].set(
                        bs.default_left, mode="drop"),
                    best_is_cat=st.best_is_cat.at[idx2].set(
                        bs.is_cat, mode="drop"),
                    best_cat_mask=st.best_cat_mask.at[idx2].set(
                        bs.cat_mask, mode="drop"),
                    best_gl=st.best_gl.at[idx2].set(
                        bs.sum_grad_left, mode="drop"),
                    best_hl=st.best_hl.at[idx2].set(
                        bs.sum_hess_left, mode="drop"),
                    best_cl=st.best_cl.at[idx2].set(
                        bs.count_left, mode="drop"),
                ), row_leaf

        def cond(st: _GrowState):
            room = st.num_leaves < L
            more = jnp.max(st.best_gain) > _NEG_INF
            if n_forced:
                more = more | (st.num_leaves - 1 < n_forced)
            return room & more

        if not route:
            state = jax.lax.while_loop(cond, lambda st: step(st)[0], state)
            return _finish(state), _row_leaf_from_perm(state, n, max_bucket)
        n_all = bins_fm_all.shape[1] - 1
        state, row_leaf = jax.lax.while_loop(
            lambda c: cond(c[0]), lambda c: step(*c),
            (state, jnp.zeros(n_all + 1, jnp.int32)))
        return _finish(state), row_leaf[:n_all]

    # ------------------------------------------------------------------ mask path
    def _grow_mask(bins, vals, scale3, feature_mask, meta, plan, cegb=None,
                   key=None):
        """Mask-layout growth (sharding-friendly; full-N pass per split).
        Under a mesh it runs on GSPMD-sharded operands OUTSIDE shard_map,
        which is why the plan names a partitionable histogram there."""
        n, gcols = bins.shape
        f = meta[0].shape[0]
        groups_mat = _groups_matrix(f) if use_groups else None

        @phase("grow/hist")
        def hist_for(mask):
            # vals already carries bagging weights + in-bag zeroing; the
            # per-leaf predicate is the only extra mask needed.  RAW output;
            # scaling happens at split-scan consumption.
            masked = jnp.where(mask[:, None], vals, jnp.zeros_like(vals))
            return histogram_from_vals(
                bins, masked, num_bins=HB,
                impl=plan.hist_impl, rows_block=cfg.rows_block)

        with phase("grow/setup"):
            nan_bins = meta[1]
            root_hist = histogram_from_vals(
                bins, vals, num_bins=HB, impl=plan.hist_impl,
                rows_block=cfg.rows_block)
            root_tot = jnp.sum(_scale_hist(root_hist[0:1], scale3)[0], axis=0)
            root_g, root_h, root_c = root_tot[0], root_tot[1], root_tot[2]
            state = _init_state(n, f, gcols, root_hist, root_g, root_h, root_c,
                                key)
            row_leaf0 = jnp.zeros(n, jnp.int32)
            root_pen = None
            if cfg.split.use_cegb and cegb is not None:
                root_pen = _cegb_penalty(root_c, state.feat_used,
                                         state.leaf_path[0], *cegb)
            state, root_bs = _root_best(state, scale3, meta, feature_mask,
                                        root_pen, groups_mat)
            state = _store_best(state, jnp.asarray(0), root_bs,
                                jnp.asarray(True))

        def body(carry):
            st, row_leaf = carry
            with phase("grow/select"):
                use_f = jnp.asarray(False)
                si = jnp.asarray(0)
                if n_forced:
                    st, use_f, si = _apply_forced(st, scale3, meta)
                    leaf = jnp.where(
                        use_f, st.forced_leaf[si],
                        jnp.argmax(st.best_gain)).astype(jnp.int32)
                else:
                    leaf = jnp.argmax(st.best_gain).astype(jnp.int32)
                node = st.num_leaves - 1
                new_leaf = st.num_leaves

                feat = st.best_feature[leaf]
                sbin = st.best_bin[leaf]
                dleft = st.best_default_left[leaf]
                scat = st.best_is_cat[leaf]
                cmask = st.best_cat_mask[leaf]

            with phase("grow/partition"):
                gcol = meta[4][feat] if cfg.bundled else feat
                col = _decode_col(
                    cfg, jnp.take(bins, gcol, axis=1).astype(jnp.int32),
                    feat, meta)
                is_nan = col == nan_bins[feat]
                go_left = jnp.where(scat, cmask[col], col <= sbin)
                go_left = jnp.where(is_nan & ~scat, dleft, go_left)
                mine = row_leaf == leaf
                row_leaf = jnp.where(mine & ~go_left, new_leaf, row_leaf)

            with phase("grow/select"):
                pg, ph, pc = (st.leaf_sum_grad[leaf], st.leaf_sum_hess[leaf],
                              st.leaf_count[leaf])
                gl, hl, cl = (st.best_gl[leaf], st.best_hl[leaf],
                              st.best_cl[leaf])
                gr, hr, cr = pg - gl, ph - hl, pc - cl

                small_is_left = cl <= cr
                target = jnp.where(small_is_left, leaf, new_leaf)
                # row_leaf tracks ALL rows (out-of-bag included, they need
                # score updates later); out-of-bag rows contribute zeros via
                # the pre-masked vals, so the count channel stays consistent
                # with the root histogram.
            hist_small = hist_for(row_leaf == target)
            with phase("grow/subtract"):
                hist_parent = st.leaf_hist[leaf]
                hist_big = hist_parent - hist_small
                hist_left = jnp.where(small_is_left, hist_small, hist_big)
                hist_right = jnp.where(small_is_left, hist_big, hist_small)

            tree = _update_tree(st, leaf, new_leaf, node, pg, ph, pc)
            st = st._replace(tree=tree)
            st = _children_updates(st, leaf, new_leaf, hist_left,
                                   hist_right, gl, hl, cl, gr, hr, cr,
                                   meta, feature_mask, cegb, groups_mat,
                                   scale3)
            if n_forced:
                st = _record_forced_children(st, use_f, si, leaf, new_leaf)
            if inter:
                st = _inter_refresh(st, scale3, meta, feature_mask, cegb,
                                    groups_mat)
            return st, row_leaf

        def cond(carry):
            st, _ = carry
            more = jnp.max(st.best_gain) > _NEG_INF
            if n_forced:
                more = more | (st.num_leaves - 1 < n_forced)
            return (st.num_leaves < L) & more

        state, row_leaf = jax.lax.while_loop(cond, body, (state, row_leaf0))
        return _finish(state), row_leaf

    # ----------------------------------------------------- feature-parallel path
    def _grow_fp(bins, vals, scale3, feature_mask, meta, plan, split_key):
        """Feature-parallel perm layout (reference
        ``FeatureParallelTreeLearner``, feature_parallel_tree_learner.cpp):
        rows replicated, feature columns sharded.  Each shard histograms and
        scans ONLY its own features (S-fold histogram compute + leaf_hist
        memory split), the winner SplitInfo syncs via one psum per scan
        (SyncUpGlobalBestSplit), and row partitions broadcast one (N,)
        go-left vector per split (the reference replicates data so its
        partitions are local; ours trades N bits/split for the sharded
        column store).  Cost per split is O(leaf rows + N), not the mask
        layout's O(N * num_leaves) full rescan."""
        from jax.sharding import PartitionSpec as P

        S = fp_shards
        fl = -(-bins.shape[1] // S)
        fp_width = fl * S
        nbpf, nanb, iscat, mono = meta[:4]
        with phase("grow/setup"):
            fmask = feature_mask
            if bins.shape[1] != fp_width:
                # dummy columns: all-zero bins (callers may pre-pad bins once)
                bins = jnp.pad(bins, ((0, 0), (0, fp_width - bins.shape[1])))
            padm = fp_width - nbpf.shape[0]
            if padm:
                # pad metadata to the bins width; mask False = never selectable
                fmask = jnp.pad(fmask, (0, padm))
                nbpf = jnp.pad(nbpf, (0, padm), constant_values=2)
                nanb = jnp.pad(nanb, (0, padm), constant_values=HB)
                iscat = jnp.pad(iscat, (0, padm))
                mono = jnp.pad(mono, (0, padm))
        have_scale = scale3 is not None
        have_key = split_key is not None
        extras, especs = [], []
        if have_scale:
            extras.append(scale3)
            especs.append(P())
        if have_key:
            extras.append(split_key)
            especs.append(P())

        def body(bins_l, vals_r, fm_l, nb_l, na_l, ic_l, mo_l, *extra):
            i = 0
            s3 = sk = None
            if have_scale:
                s3 = extra[i]
                i += 1
            if have_key:
                sk = extra[i]
            return _grow_wave(bins_l, vals_r, s3, fm_l,
                              (nb_l, na_l, ic_l, mo_l), plan, None, sk,
                              faxis=fp_axis_name)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, fp_axis_name), P(), P(fp_axis_name),
                      P(fp_axis_name), P(fp_axis_name), P(fp_axis_name),
                      P(fp_axis_name)) + tuple(especs),
            out_specs=(P(), P()),
            check_vma=False)(bins, vals, fmask, nbpf, nanb, iscat, mono,
                             *extras)

    # -------------------------------------------------------------- sharded path
    def _grow_sharded(bins, vals, scale3, feature_mask, meta, plan, cegb,
                      split_key):
        """Run the wave grower per-shard under ``shard_map``:
        local partitions + local histograms, ONE cross-shard histogram
        reduction per wave (the reference's histogram reduce,
        ``data_parallel_tree_learner.cpp:284``) — a feature-sliced
        ``psum_scatter`` + slice-local scan + SplitInfo payload sync by
        default, or a full ``psum`` + replicated scan under
        ``hist_comm=allreduce``.  Either way every split decision lands
        replicated on all shards, so the tree state is replicated and the
        while_loop stays in lockstep."""
        from jax.sharding import PartitionSpec as P

        have_scale = scale3 is not None
        have_cegb = cegb is not None
        have_key = split_key is not None
        extras, especs = [], []
        if have_scale:
            extras.append(scale3)
            especs.append(P())
        if have_cegb:
            extras.extend(cegb)
            especs.extend([P(), P()])
        if have_key:
            extras.append(split_key)
            especs.append(P())

        n_meta = len(meta)

        def body(bins, vals, fmask, *rest):
            m = rest[:n_meta]
            extra = rest[n_meta:]
            i = 0
            s3 = cg = sk = None
            if have_scale:
                s3 = extra[i]
                i += 1
            if have_cegb:
                cg = (extra[i], extra[i + 1])
                i += 2
            if have_key:
                sk = extra[i]
            return _grow_wave(bins, vals, s3, fmask, m, plan, cg, sk,
                              axis=data_axis)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(data_axis), P(data_axis), P())
            + (P(),) * n_meta + tuple(especs),
            out_specs=(P(), P(data_axis)),
            check_vma=False,
        )(bins, vals, feature_mask, *meta, *extras)

    def _grow_impl(
        bins: jnp.ndarray,          # (N, F) uint8/16 — binned features
        grad: jnp.ndarray,          # (N,) f32
        hess: jnp.ndarray,          # (N,) f32
        sample_mask: jnp.ndarray,   # (N,) f32 bagging/GOSS weights (1.0 = in-bag)
        feature_mask: jnp.ndarray,  # (F,) bool feature_fraction mask
        num_bins_per_feature: jnp.ndarray,
        nan_bins: jnp.ndarray,
        is_categorical: jnp.ndarray,
        monotone: jnp.ndarray,      # (F,) i32
        cegb_coupled: Optional[jnp.ndarray] = None,  # (F,) f32 (CEGB)
        cegb_lazy: Optional[jnp.ndarray] = None,     # (F,) f32 (CEGB)
        quant_key: Optional[jnp.ndarray] = None,     # PRNG key (quantized)
        split_key: Optional[jnp.ndarray] = None,     # PRNG key
                                                     # (extra_trees / bynode)
        feat_group: Optional[jnp.ndarray] = None,    # (F,) i32 (EFB)
        feat_offset: Optional[jnp.ndarray] = None,   # (F,) i32 (EFB)
        sample_rows: Optional[jnp.ndarray] = None,   # (K,) i32 in-bag row
                                                     # ids (plan.sampling
                                                     # == "subset")
        levels_out: bool = False,   # Python: under quantized training also
                                    # return (gq, hq, g_scale, h_scale)
    ) -> Tuple[TreeArrays, jnp.ndarray]:
        meta = (num_bins_per_feature, nan_bins, is_categorical, monotone)
        if cfg.bundled:
            if feat_group is None or feat_offset is None:
                raise ValueError("bundled grower needs feat_group/feat_offset")
            meta = meta + (feat_group, feat_offset)
        cegb = None
        if cfg.split.use_cegb:
            f = num_bins_per_feature.shape[0]
            coupled = (cegb_coupled if cegb_coupled is not None
                       else jnp.zeros(f, jnp.float32))
            lazy = (cegb_lazy if cegb_lazy is not None
                    else jnp.zeros(f, jnp.float32))
            cegb = (coupled, lazy)
        levels = None
        with phase("grow/setup"):
            g = grad * sample_mask
            h = hess * sample_mask
            in_bag = sample_mask > 0.0
            if cfg.quantized:
                # Reference GradientDiscretizer (gradient_discretizer.hpp:128):
                # int8 levels + per-iteration scales; histograms accumulate s32
                # and are rescaled to f32 right before the split scan.
                from ..ops.quantize import (discretize_gradients,
                                            gradient_scales)
                if quant_key is None:
                    quant_key = jax.random.PRNGKey(0)
                with segment("quantize"):
                    g_scale, h_scale = gradient_scales(
                        g, h, cfg.num_grad_quant_bins)
                    gq, hq = discretize_gradients(g, h, g_scale, h_scale,
                                                  quant_key,
                                                  cfg.stochastic_rounding)
                    vals = jnp.stack([gq, hq, in_bag.astype(jnp.int8)],
                                     axis=-1)
                    scale3 = jnp.stack(
                        [g_scale, h_scale, jnp.asarray(1.0, jnp.float32)])
                levels = (gq, hq, g_scale, h_scale)
            else:
                vals = jnp.stack([g, h, in_bag.astype(jnp.float32)], axis=-1)
                scale3 = None
            # Defined rounding for the histogram inputs (docs/STREAMING.md):
            # without the barrier XLA may fuse the grad*sample_mask multiply
            # into the histogram scatter-add as an FMA — a per-program 1-ULP
            # coin flip the streamed chunk programs cannot replicate (it only
            # surfaces when the mask is inexact, e.g. GOSS amplification).
            # Materialized vals make every downstream histogram an adds-only
            # fold, the one arithmetic all layouts and the stream kit share.
            vals = jax.lax.optimization_barrier(vals)
        if need_key and split_key is None:
            split_key = jax.random.PRNGKey(0)
        n = grad.shape[0]
        dshards = 1 if mesh is None else int(mesh.shape[data_axis])
        with phase("grow/setup"):
            if mesh is not None and cfg.gather_rows:
                # shard_map needs even row shards; zero-valued pad rows
                # contribute nothing to any histogram.  Callers avoid the bins
                # copy by pre-padding the bins array once.
                pad = (-bins.shape[0]) % dshards
                if pad:
                    bins = jnp.pad(bins, ((0, pad), (0, 0)))
            if bins.shape[0] != vals.shape[0]:
                vals = jnp.pad(
                    vals, ((0, bins.shape[0] - vals.shape[0]), (0, 0)))
        # The ONE routing chain: the plan, completed for the shapes this
        # program is traced at (the row floor and the kernel's width gate).
        nfeat = meta[0].shape[0]
        plan = plan_growth(cfg, mesh, data_axis, rows=bins.shape[0],
                           features=nfeat)
        # What the traced width selects, for the registry (set once a
        # compile, as rank.queries is): the column chunks one histogram
        # takes and the columns ONE launch holds (kernel_rows' chunks<K>
        # and cols<C>), and the split scan's block width (0 = untiled).
        hcols = ftile = nfeat if cfg.packed4 else bins.shape[1]
        if plan.hist_impl == "pallas":
            from ..ops.pallas_histogram import kernel_layout
            ftile = kernel_layout(hcols, HB,
                                  "int8" if cfg.quantized else "f32",
                                  cfg.rows_block, cfg.packed4)[1]
        registry().gauge("hist.col_chunks").set(-(-hcols // ftile))
        registry().gauge("hist.cols_tile").set(ftile)
        # the bits of the values the histograms contract: int8 levels
        # (int32 sums) under quantized training, float32 otherwise
        registry().gauge("hist.dtype").set(8 if cfg.quantized else 32)
        if cfg.quantized:
            registry().gauge("quant.bins").set(cfg.num_grad_quant_bins)
            registry().gauge("quant.stochastic").set(
                int(cfg.stochastic_rounding))
        registry().gauge("scan.tile").set(
            _resolve_tile(cfg.split.scan_tile, nfeat))
        if plan.layout == "feature":
            tree, row_leaf = _grow_fp(bins, vals, scale3, feature_mask,
                                      meta, plan, split_key)
        elif plan.layout == "data":
            tree, row_leaf = _grow_sharded(bins, vals, scale3, feature_mask,
                                           meta, plan, cegb, split_key)
        elif plan.body == "wave":
            if sample_rows is not None and plan.sampling != "subset":
                raise ValueError(f"sample_rows handed to a grower whose "
                                 f"plan keeps the mask: {plan}")
            tree, row_leaf = _grow_wave(bins, vals, scale3, feature_mask,
                                        meta, plan, cegb, split_key,
                                        rows=sample_rows)
        else:
            if cfg.packed4:
                # the mask body (tiny row counts / no-gather) indexes
                # full columns; unpack once — small data, small cost
                bins = unpack_bins4(bins, nfeat)
            elif static.layout == "feature":
                # the caller may have padded feature columns for the
                # feature-sharded layout, which the row count then
                # refused: the mask body must see the metadata's width
                # (pad columns are all-zero)
                bins = bins[:, :nfeat]
            tree, row_leaf = _grow_mask(bins, vals, scale3, feature_mask,
                                        meta, plan, cegb, split_key)
        with phase("grow/finish"):
            row_leaf = row_leaf[:n]
            if cfg.quantized and cfg.quant_renew_leaf:
                # quant_train_renew_leaf: recompute leaf outputs from the TRUE
                # (unquantized) gradients (reference RenewIntGradTreeOutput).
                g_leaf = jax.ops.segment_sum(g, row_leaf, num_segments=L)
                h_leaf = jax.ops.segment_sum(h, row_leaf, num_segments=L)
                renewed = leaf_output(g_leaf, h_leaf, cfg.split)
                active = jnp.arange(L) < tree.num_leaves
                tree = tree._replace(
                    leaf_value=jnp.where(active, renewed, 0.0),
                    leaf_weight=jnp.where(active, h_leaf, 0.0))
        if levels_out and levels is not None:
            return tree, row_leaf, levels
        return tree, row_leaf

    # ------------------------------------------------- streaming grow kit
    # Chunked histogram accumulation hook (lightgbm_tpu/stream/,
    # docs/STREAMING.md): the mask-layout growth body decomposed into
    # jitted pieces whose only full-N inputs are row-separable — a
    # host-driven driver sweeps bins CHUNKS through ``chunk_root`` /
    # ``chunk_step`` under a byte budget while the decision state
    # (``_GrowState``) stays device-resident and O(L).  Every piece reuses
    # the SAME split/selection/update functions the in-core layouts trace
    # (_init_state/_root_best/_update_tree/_children_updates/_finish), so
    # a streamed tree's decisions are the in-core tree's decisions
    # whenever the chunk-accumulated histogram sums equal the in-core
    # ones — unconditionally for quantized int32 histograms, and exactly
    # for fp32 whenever the sums are exactly representable (the same
    # caveat as the histogram pool and fused wave kernel carry).
    def _make_stream_kit(num_features: int):
        if static.stream_reason is not None:
            raise ValueError("streaming growth unsupported: "
                             + static.stream_reason)
        f = int(num_features)
        hist_kw = dict(num_bins=HB, impl=static.hist_impl,
                       rows_block=cfg.rows_block, packed4=cfg.packed4,
                       features=f if cfg.packed4 else 0)

        @phase("grow/setup")
        def _prep(grad, hess, sample_mask, quant_key=None):
            """(vals, scale3) for one tree — the exact _grow_impl prologue
            (GOSS/bagging weights folded, quantized discretization keyed
            identically), shared so streamed and in-core gradients can
            never diverge."""
            g = grad * sample_mask
            h = hess * sample_mask
            in_bag = sample_mask > 0.0
            if cfg.quantized:
                from ..ops.quantize import (discretize_gradients,
                                            gradient_scales)
                if quant_key is None:
                    quant_key = jax.random.PRNGKey(0)
                with segment("quantize"):
                    g_scale, h_scale = gradient_scales(
                        g, h, cfg.num_grad_quant_bins)
                    gq, hq = discretize_gradients(g, h, g_scale, h_scale,
                                                  quant_key,
                                                  cfg.stochastic_rounding)
                    vals = jnp.stack([gq, hq, in_bag.astype(jnp.int8)],
                                     axis=-1)
                    scale3 = jnp.stack(
                        [g_scale, h_scale, jnp.asarray(1.0, jnp.float32)])
                return jax.lax.optimization_barrier(vals), scale3
            vals = jnp.stack([g, h, in_bag.astype(jnp.float32)], axis=-1)
            # same barrier as _grow_impl: histogram inputs materialize,
            # so chunked folds replay the in-core adds exactly
            return jax.lax.optimization_barrier(vals), None

        @phase("grow/hist")
        def _chunk_root(acc, bins_c, vals_c, count):
            """Accumulate one chunk's rows into the root histogram.
            ``count`` masks the static-shape pad tail: the driver slices
            ``vals`` from the full device vector, so a short chunk's pad
            slots alias the NEXT chunk's rows and must contribute zero.
            ``acc`` seeds the histogram (``init=``), so the cross-chunk
            fold replays the one-call add order exactly."""
            valid = jnp.arange(vals_c.shape[0], dtype=jnp.int32) < count
            vals_c = jnp.where(valid[:, None], vals_c,
                               jnp.zeros_like(vals_c))
            return histogram_from_vals(bins_c, vals_c, init=acc, **hist_kw)

        @phase("grow/setup")
        def _sk_init(root_hist, n_rows, scale3=None, meta=None,
                     feature_mask=None, key=None):
            # exact _grow_mask root block: per-channel totals from feature
            # 0's bins, shared root-best scan, stored at leaf 0
            root_tot = jnp.sum(_scale_hist(root_hist[0:1], scale3)[0],
                               axis=0)
            root_g, root_h, root_c = root_tot[0], root_tot[1], root_tot[2]
            state = _init_state(n_rows, f, root_hist.shape[0], root_hist,
                                root_g, root_h, root_c, key)
            state, root_bs = _root_best(state, scale3, meta, feature_mask,
                                        None, None)
            return _store_best(state, jnp.asarray(0), root_bs,
                               jnp.asarray(True))

        @phase("grow/select")
        def _sk_select(st):
            """This step's split decision, read from the resident state —
            the scalars every chunk's partition/histogram pass consumes."""
            leaf = jnp.argmax(st.best_gain).astype(jnp.int32)
            new_leaf = st.num_leaves
            cl = st.best_cl[leaf]
            cr = st.leaf_count[leaf] - cl
            small_is_left = cl <= cr
            target = jnp.where(small_is_left, leaf, new_leaf)
            return (leaf, new_leaf, st.best_feature[leaf],
                    st.best_bin[leaf], st.best_default_left[leaf],
                    st.best_is_cat[leaf], st.best_cat_mask[leaf],
                    target, small_is_left)

        @phase("grow/hist")
        def _sk_chunk(acc, bins_c, vals_c, row_leaf_c, sel, nan_bins):
            """One chunk's share of one split: partition update for the
            chunk's rows + the smaller sibling's partial histogram.  Pad
            rows carry ``row_leaf == -1`` and contribute nothing."""
            (leaf, new_leaf, feat, sbin, dleft, scat, cmask,
             target, _sl) = sel
            with phase("grow/partition"):
                if cfg.packed4:
                    byte = jnp.take(bins_c, feat // 2,
                                    axis=1).astype(jnp.int32)
                    col = jnp.where(feat % 2 == 0, byte & 15,
                                    (byte >> 4) & 15)
                else:
                    col = jnp.take(bins_c, feat, axis=1).astype(jnp.int32)
                is_nan = col == nan_bins[feat]
                go_left = jnp.where(scat, cmask[col], col <= sbin)
                go_left = jnp.where(is_nan & ~scat, dleft, go_left)
                mine = row_leaf_c == leaf
                row_leaf_c = jnp.where(mine & ~go_left, new_leaf, row_leaf_c)
            mask = row_leaf_c == target
            masked = jnp.where(mask[:, None], vals_c,
                               jnp.zeros_like(vals_c))
            acc = histogram_from_vals(bins_c, masked, init=acc, **hist_kw)
            return acc, row_leaf_c

        @phase("grow/subtract")
        def _sk_apply(st, sel, hist_small, scale3=None, meta=None,
                      feature_mask=None):
            """Execute the selected split from the chunk-accumulated
            smaller-sibling histogram — the exact mask-layout body tail."""
            (leaf, new_leaf, _feat, _sbin, _dleft, _scat, _cmask,
             _target, small_is_left) = sel
            node = st.num_leaves - 1
            pg, ph, pc = (st.leaf_sum_grad[leaf], st.leaf_sum_hess[leaf],
                          st.leaf_count[leaf])
            gl, hl, cl = st.best_gl[leaf], st.best_hl[leaf], st.best_cl[leaf]
            gr, hr, cr = pg - gl, ph - hl, pc - cl
            hist_parent = st.leaf_hist[leaf]
            hist_big = hist_parent - hist_small
            hist_left = jnp.where(small_is_left, hist_small, hist_big)
            hist_right = jnp.where(small_is_left, hist_big, hist_small)
            tree = _update_tree(st, leaf, new_leaf, node, pg, ph, pc)
            st = st._replace(tree=tree)
            return _children_updates(st, leaf, new_leaf, hist_left,
                                     hist_right, gl, hl, cl, gr, hr, cr,
                                     meta, feature_mask, None, None, scale3)

        @phase("grow/select")
        def _sk_probe(st):
            """(num_leaves, max_gain) — the while-loop condition scalars
            (the streaming driver's one tiny host sync per split)."""
            return st.num_leaves, jnp.max(st.best_gain)

        import types
        return types.SimpleNamespace(
            prep=jax.jit(_prep),
            chunk_root=jax.jit(_chunk_root),
            init=jax.jit(_sk_init),
            select=jax.jit(_sk_select),
            chunk_step=jax.jit(_sk_chunk),
            apply=jax.jit(_sk_apply),
            probe=jax.jit(_sk_probe),
            finish=jax.jit(_finish),
            hist_dtype=(jnp.int32 if cfg.quantized else jnp.float32),
            hist_shape=(f, HB, 3),
            max_leaves=L,
            packed4=cfg.packed4,
            quantized=cfg.quantized,
        )

    # Telemetry span at the ONE dispatch boundary (telemetry/spans.py):
    # the whole wave loop — histogram build, sibling subtract, split scan,
    # partition — is a single compiled program, so the host-side span
    # wraps its launch and the per-phase breakdown inside it comes from
    # the jax.profiler trace (tpu_profile_iters), not extra dispatches.
    # Host-only instrumentation: the compiled program is bitwise-identical
    # with telemetry on, off, or absent (tests/test_telemetry.py).
    from ..telemetry import instrument
    grow = instrument(jax.jit(_grow_impl, donate_argnums=()), "grower/grow",
                      track_memory=True)
    # the static half of the growth plan (shape gates open), inspectable
    # by tests/tools; GBDT.plan is the one completed for its data
    grow.plan = static
    grow.pool_slots = _pool_slots
    # Scan-able handle: the iteration-packed path traces grow INSIDE a
    # lax.scan body that is already under jit; the raw function skips the
    # redundant inner-jit trace (semantics identical — nested jit inlines).
    grow.raw = _grow_impl
    # Streaming grow kit factory (lightgbm_tpu/stream/): chunked twin of
    # the mask-layout body, sharing its state/update/scan functions.
    grow.stream_kit = _make_stream_kit
    return grow
