"""CPU-hermetic HLO cost-model regression harness.

The end-to-end TPU number depends on chip availability; these tests pin the
*compiled program's* cost structure so a perf regression (a per-leaf
sequential ladder, a duplicated leaf-histogram buffer, an oversized per-wave
collective, a histogram that silently de-quantizes) fails CI on any
platform, chip or no chip.

Technique: compile the bench-shaped grower (255 leaves, leaf_batch=16,
28 features, 256 bins — BASELINE.md's Higgs config) with XLA:CPU and parse
the optimized HLO text.  The wave while-loop body appears exactly once in
the HLO regardless of trip count, so per-wave tensor shapes, carry buffers
and collective volumes are all statically checkable.

Reference perf anchors: docs/Experiments.rst:113 (Higgs speed table) and
src/treelearner/data_parallel_tree_learner.cpp:284 (one histogram reduce
per step).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu.models.grower as G
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import TrainData
from lightgbm_tpu.models.gbdt import _split_config
from lightgbm_tpu.parallel.mesh import DATA_AXIS, make_mesh

# Bench shape (BASELINE.md: Higgs 28 features; bench.py: 255 leaves,
# leaf_batch 16, 256 bins).  N only has to be big enough to keep every
# bucket branch alive; the sharded compile needs > _MIN_BUCKET (2048)
# rows per shard or make_grower falls back to the mask layout.
N, F, B, L, W = 8192, 28, 256, 255, 16
N_SHARDED = 8 * 4096

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "u16": 2, "bf16": 2,
                "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = int(np.prod([int(d) for d in dims.split(",") if d])) if dims else 1
    return _DTYPE_BYTES[dtype] * n


def _parse_shapes(txt: str):
    return re.findall(
        r"(pred|s8|u8|u16|bf16|f32|s32|u32|f64|s64|u64)\[([0-9,]*)\]", txt)


@pytest.fixture(scope="module")
def hlo():
    """Compiled HLO of the bench-shaped wave grower: fp32 serial, quantized
    serial, and fp32 8-way data-parallel under both histogram-comm
    lowerings (auto -> feature-sliced reduce-scatter; explicit
    allreduce)."""
    cfg = Config({"objective": "binary", "verbosity": -1})

    def compile_text(quantized=False, want_cost=False):
        rng = np.random.RandomState(0)
        X = rng.randn(N, F)
        y = (X[:, 0] > 0).astype(np.float64)
        td = TrainData.build(X, y, cfg)
        meta = td.feature_meta_device()
        gcfg = G.GrowerConfig(num_leaves=L, num_bins=B,
                              split=_split_config(cfg), leaf_batch=W,
                              quantized=quantized)
        grow = G.make_grower(gcfg)
        args = [jnp.asarray(td.binned.bins), jnp.zeros(N, jnp.float32),
                jnp.ones(N, jnp.float32), jnp.ones(N, jnp.float32),
                jnp.ones(F, bool), meta["num_bins_per_feature"],
                meta["nan_bins"], meta["is_categorical"], meta["monotone"]]
        compiled = grow.lower(*args).compile()
        txt = compiled.as_text()
        if not want_cost:
            return txt, None
        cost = compiled.cost_analysis()
        return txt, (cost[0] if isinstance(cost, list) else cost)

    def compile_sharded(hist_comm):
        # ONE compile harness shared with tools/comm_census.py so the
        # census tool and CI pin the SAME program.
        from tools.comm_census import compile_sharded_grower_hlo
        txt = compile_sharded_grower_hlo(
            hist_comm, n_shards=8, rows_per_shard=N_SHARDED // 8,
            features=F, num_leaves=L, leaf_batch=W, num_bins=B)
        # Guard against the mask-layout fallback silently compiling a
        # collective-free program (rows/shard must exceed _MIN_BUCKET).
        assert "all-reduce" in txt or "reduce-scatter" in txt
        return txt

    fp32, fp32_cost = compile_text(want_cost=True)
    quant, _ = compile_text(quantized=True)
    sharded = compile_sharded("auto")
    sharded_ar = compile_sharded("allreduce")
    return {"fp32": fp32, "quant": quant, "sharded": sharded,
            "sharded_ar": sharded_ar, "fp32_cost": fp32_cost}


def _whiles(txt):
    """Carry-tuple type strings of every while op."""
    return re.findall(r"= \(([^)]*)\) while\(", txt)


def _hist_whiles(txt, hist_shape):
    """Every while carry holding the leaf histogram.  Current jaxlib
    fissions the growth loop — the double-buffered (2W, F, B, 3) wave
    carry rides in a while of its own beside the main growth loop — so
    the structural invariants below quantify over ALL hist-carrying
    loops instead of pinning their count (that count is XLA scheduling,
    not program structure)."""
    matches = [w for w in _whiles(txt) if hist_shape in w]
    assert matches, "no while carries the leaf histogram"
    return matches


def test_wave_batches_w_leaves_per_step(hlo):
    """The wave body histograms W=16 smaller siblings per sequential step:
    the (W, F, B, 3) batched histogram tensor must exist.  A reintroduced
    per-leaf ladder (leaf_batch silently ignored) removes this shape and
    multiplies sequential steps by W."""
    assert f"f32[{W},{F},{B},3]" in hlo["fp32"]
    assert f"s32[{W},{F},{B},3]" in hlo["quant"]


def test_single_leaf_hist_buffer_in_carry(hlo):
    """Exactly ONE (L, F, B, 3) histogram buffer lives in the growth loop's
    carry — a second copy (e.g. an M-packed kernel's staging buffer or a
    defensive clone) doubles the dominant HBM resident."""
    hist = f"f32[{L},{F},{B},3]"
    for carry in _hist_whiles(hlo["fp32"], hist):
        assert carry.count(hist) == 1, carry.count(hist)


def test_growth_carry_bytes_bounded(hlo):
    """EVERY hist-carrying loop's carry stays within 10% + 4 MB of the
    leaf_hist buffer itself (leaf_hist dominates by design; everything
    else is O(N + L*B) — incl. the fissioned double-buffered (2W, F, B, 3)
    wave carry, which is W/L of the hist)."""
    hist_bytes = L * F * B * 3 * 4
    for carry in _hist_whiles(hlo["fp32"], f"f32[{L},{F},{B},3]"):
        total = sum(_shape_bytes(d, s) for d, s in _parse_shapes(carry))
        assert total <= hist_bytes * 1.10 + (4 << 20), (total, hist_bytes)


def test_growth_carry_bytes_bounded_wide_pool():
    """ISSUE-4 hermetic pin at the wide-feature shape (255 leaves, F=700,
    B=256 — the Yahoo-LTR histogram geometry that motivates the bounded
    pool): with ``histogram_pool_size`` set, the growth loop's carried
    histogram bytes must be <= 1/4 of the unpooled (L, F, B, 3) carry
    (~523 MB f32), and no full-L histogram buffer may be smuggled back
    into the program anywhere (a defensive copy or a staging buffer would
    resurrect exactly the memory wall the pool removes).  The compile also
    exercises the feature-tiled split scan (auto-engaged at F=700)."""
    NW, FW, LW, WW = 4096, 700, 255, 4
    POOL_MB = 128.0
    gcfg = G.GrowerConfig(
        num_leaves=LW, num_bins=B,
        split=G.SplitConfig(has_nan=False, has_categorical=False,
                            use_sorted_categorical=False,
                            has_monotone=False),
        leaf_batch=WW, histogram_pool_size=POOL_MB)
    grow = G.make_grower(gcfg)
    P = grow.pool_slots(FW)
    unpooled_bytes = LW * FW * B * 3 * 4
    assert grow.plan.pool
    assert P * FW * B * 3 * 4 <= unpooled_bytes // 4, (P, LW)
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, B, (NW, FW)).astype(np.uint8))
    args = [bins, jnp.zeros(NW, jnp.float32), jnp.ones(NW, jnp.float32),
            jnp.ones(NW, jnp.float32), jnp.ones(FW, bool),
            jnp.full(FW, B, jnp.int32), jnp.full(FW, B, jnp.int32),
            jnp.zeros(FW, bool), jnp.zeros(FW, jnp.int32)]
    txt = grow.lower(*args).compile().as_text()
    pool_hist = f"f32[{P},{FW},{B},3]"
    carries = [w for w in _whiles(txt) if pool_hist in w]
    assert carries, "pool histogram buffer missing from the growth carry"
    # The growth loop is the largest carry holding the pool buffer (inner
    # fori-loops may carry it as a loop-invariant operand).
    grow_carry_hist = max(
        sum(_shape_bytes(d, s) for d, s in _parse_shapes(w)
            if int(np.prod([int(x) for x in s.split(",") if x])
                   if s else 1) >= P * FW * B)
        for w in carries)
    assert grow_carry_hist <= unpooled_bytes // 4, (
        grow_carry_hist, unpooled_bytes)
    # no second histogram-scale buffer: nothing full-L-sized anywhere
    assert f"[{LW},{FW},{B},3]" not in txt


def test_while_op_count_bounded(hlo):
    """The loop count must not scale with the leaf ladder: the guarded
    regression is an unrolled per-leaf program (>= L = 255 loops, one per
    leaf).  Current jaxlib fissions the grow loop and the histogram block
    scans into ~51 small whiles (scheduling drift, not structure), so the
    bound is a fraction of L rather than the old handful."""
    n = len(_whiles(hlo["fp32"]))
    assert n <= L // 4, f"{n} while ops vs per-leaf-ladder bound {L // 4}"


def test_quantized_hist_stays_integer(hlo):
    """Quantized training carries the leaf histograms as s32 end to end
    (reference bin.h:48-81 int histograms); an f32 leaf-hist buffer means
    something upcast inside the loop."""
    txt = hlo["quant"]
    assert f"s32[{L},{F},{B},3]" in txt
    assert f"f32[{L},{F},{B},3]" not in txt


def test_collective_bytes_per_wave(hlo):
    """The data-parallel default (tpu_hist_comm=auto -> reduce_scatter)
    feature-slices the per-wave histogram reduce (reference ReduceScatter,
    data_parallel_tree_learner.cpp:284): each shard receives only its owned
    ceil(F/K) feature block.  Pin the lowering three ways:

    1. NO full-histogram all-reduce may reappear — every all-reduce left in
       the program is payload-broadcast/scalar sized;
    2. exactly TWO histogram reduce-scatters (wave + root), whose ring-wire
       volume is (K-1)/K · (W+1)·Gp·B·3 · itemsize (Gp = F padded to a
       shard multiple);
    3. total collective wire bytes stay within that + an O(W·B)
       SplitInfo-payload term — and come in >= 1.8x under the explicit
       allreduce lowering of the same program (the ISSUE-3 acceptance
       ratio; exact 2x is eaten by the F=28 -> Gp=32 pad and the payload
       broadcasts)."""
    from tools.comm_census import collective_census

    K = 8
    rs_ops = collective_census(hlo["sharded"], K)
    ar_ops = collective_census(hlo["sharded_ar"], K)

    gp = -(-F // K) * K
    wave_hist_bytes = W * F * B * 3 * 4
    payload_budget = 4 * W * (16 + B) * 4 + (64 << 10)   # SplitInfo + scalars

    # (1) no full-histogram all-reduce in the reduce-scatter lowering
    big_ar = [o for o in rs_ops if o["op"] == "all-reduce"
              and o["payload_bytes"] >= wave_hist_bytes // 4]
    assert not big_ar, big_ar
    # ... but the allreduce lowering has it (the census tool can tell them
    # apart, so a silently-degraded rs path cannot pass)
    assert any(o["op"] == "all-reduce"
               and o["payload_bytes"] == wave_hist_bytes for o in ar_ops)

    # (2) the wave + root histogram reduce-scatters, within the ring budget
    rss = [o for o in rs_ops if o["op"] == "reduce-scatter"]
    assert len(rss) == 2, rss
    rs_hist_wire = sum(o["wire_bytes"] for o in rss)
    hist_budget = (K - 1) / K * (W + 1) * gp * B * 3 * 4
    assert rs_hist_wire <= hist_budget + 1, (rs_hist_wire, hist_budget)

    # (3) total wire budget + the >= 1.8x reduction vs allreduce
    rs_total = sum(o["wire_bytes"] for o in rs_ops)
    ar_total = sum(o["wire_bytes"] for o in ar_ops)
    assert rs_total <= hist_budget + payload_budget, (
        rs_total, hist_budget, payload_budget)
    # padded-F handicap: at F % K == 0 the ratio is ~2x (see
    # test_comm_ratio_unpadded); even with the 28 -> 32 pad it must clear
    # the wire-halving bar of 1.6x here and 1.8x unpadded
    assert ar_total >= 1.6 * rs_total, (ar_total, rs_total)


def test_comm_ratio_unpadded_and_int16_wire():
    """ISSUE-3 acceptance pair on a 4-shard mesh where F=28 divides evenly
    (no pad handicap):

    - the reduce-scatter lowering moves >= 1.8x fewer collective wire
      bytes per wave than the allreduce lowering of the same program;
    - under quantized training the reduce-scattered histogram rides the
      wire as int16 (reference Int16HistogramSumReducer, bin.h:48-81)
      with the int32 exact-overflow fallback branch alongside."""
    from tools.comm_census import (census_summary,
                                   compile_sharded_grower_hlo)

    K = 4
    kw = dict(n_shards=K, rows_per_shard=4096, features=F, num_leaves=63,
              leaf_batch=8)
    ar = census_summary(compile_sharded_grower_hlo("allreduce", **kw), K)
    rs = census_summary(compile_sharded_grower_hlo("reduce_scatter", **kw),
                        K)
    ratio = ar["comm_bytes_per_wave"] / rs["comm_bytes_per_wave"]
    assert ratio >= 1.8, (ratio, ar, rs)

    quant = compile_sharded_grower_hlo("reduce_scatter", quantized=True,
                                       **kw)
    # the guarded int16 wire branch AND its int32 fallback both lower
    assert re.search(r"s16\[[0-9,]*\][^=]*reduce-scatter", quant)
    assert re.search(r"s32\[[0-9,]*\][^=]*reduce-scatter", quant)


# the fused-vs-unfused structural pins' shape
NW, FW, BW, LW, WW = 4096, 12, 64, 63, 8


@pytest.fixture(scope="module")
def wave_pair():
    """Compiled text of the SAME small wave grower, fused and unfused."""
    scfg = G.SplitConfig(has_nan=False, has_categorical=False,
                         use_sorted_categorical=False, has_monotone=False,
                         min_data_in_leaf=1)
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, BW, (NW, FW)).astype(np.uint8))
    args = [bins, jnp.zeros(NW, jnp.float32), jnp.ones(NW, jnp.float32),
            jnp.ones(NW, jnp.float32), jnp.ones(FW, bool),
            jnp.full(FW, BW, jnp.int32), jnp.full(FW, BW, jnp.int32),
            jnp.zeros(FW, bool), jnp.zeros(FW, jnp.int32)]

    def compile_txt(mode, hist_impl="auto"):
        gcfg = G.GrowerConfig(num_leaves=LW, num_bins=BW, split=scfg,
                              leaf_batch=WW, wave_kernel=mode,
                              histogram_impl=hist_impl)
        grow = G.make_grower(gcfg)
        assert grow.plan.fused == (mode == "fused")
        assert hist_impl in ("auto", grow.plan.hist_impl)
        return grow.lower(*args).compile().as_text()

    # "ragged": the unfused wave beside the Pallas kernel (interpreted),
    # the program of the benchmark's unfused cells
    return {"fused": compile_txt("fused"), "unfused": compile_txt("unfused"),
            "ragged": compile_txt("unfused", "pallas")}


def test_fused_wave_no_hbm_scan_roundtrip(wave_pair):
    """ISSUE-7 structural pin: the fused wave program must not round-trip
    the batched child histograms through HBM between build and scan.  The
    unfused wave feeds all 2W children's (F, B) cumsum/gain tables through
    a vmapped best_split — the (2W, F, B) f32 scan buffers are its
    signature shape; the fused program scans per leaf INSIDE the kernel
    (interpret mode inlines it as per-grid-step (F, b_pad) blocks), so no
    wave-batched scan tensor may exist anywhere in the compiled text."""
    fused, unfused = wave_pair["fused"], wave_pair["unfused"]
    scan_buf = f"f32[{2 * WW},{FW},{BW}]"
    assert scan_buf in unfused, "unfused signature shape missing"
    assert scan_buf not in fused, (
        "fused wave program materializes the batched HBM scan tensor")
    # the unfused build batches all W smaller siblings into one HBM
    # tensor; the fused kernel accumulates per leaf in VMEM, so the only
    # wave-batched histogram left is the (W, 2, ...) child writeback
    assert f"f32[{WW},{FW},{BW},3]" in unfused


def test_fused_wave_gathers_no_more_than_a_wave_holds(wave_pair):
    """ISSUE-26 structural pin: the compiled fused program hands the
    gather and the kernel the rows the wave has.  No gathered-bins tensor
    anywhere in it has more rows than a wave can hold — half the rows (the
    smaller siblings of disjoint leaves) plus one row block per slot — or
    than the data itself; the (W, S, F) form, W x the wave's largest
    bucket (at this shape 16 384 and 32 768 rows), is gone."""
    from lightgbm_tpu.ops.pallas_wave import wave_layout

    blk = wave_layout(FW, BW, "f32")["rows_block"]
    cap = (NW // (2 * blk) + WW) * blk
    assert cap < WW * G._MIN_BUCKET       # below the old form's SMALLEST
    rows = {int(np.prod([int(d) for d in dims.split(",")][:-1]))
            for dt, dims in _parse_shapes(wave_pair["fused"])
            if dt == "u8" and dims.endswith(f",{FW}")}
    assert cap in rows, sorted(rows)      # the ladder's top step is there
    assert max(rows) <= max(cap, NW + 1), sorted(rows)
    assert not re.search(rf"u8\[{WW},\d+,{FW}\]", wave_pair["fused"])


def _scoped(txt, scope):
    return [ln for ln in txt.splitlines() if scope in ln]


def test_unfused_pallas_wave_is_one_ragged_launch_per_wave(wave_pair):
    """ISSUE-34 structural pin: beside the Pallas kernel the unfused wave
    histograms its W smaller siblings in ONE gather and ONE launch — a
    ``switch`` over the steps of one total-row ladder in granules of
    ``_WAVE_GRANULE`` rows — and no ``fori_loop`` of W per-leaf launches
    is left: nothing under ``grow/hist`` is shaped by a power-of-two
    bucket, and no per-slot result is written into the ``(W, F, B, 3)``
    batch (the old form: per slot a ``switch`` over buckets of 2 048 and
    4 096 rows here, a gather at the bucket, a launch over all of it and
    ``hs.at[j].set(h)``).  The implementations without a ragged form keep
    that loop."""
    from lightgbm_tpu.ops.pallas_histogram import kernel_layout

    blk = kernel_layout(FW, BW, "f32", 16384)[0]
    gran = max(G._WAVE_GRANULE, blk)
    ladder = G._ragged_wave_totals(NW // 2, WW, gran)
    buckets = set(G._split_buckets(NW))
    assert not buckets & set(ladder)            # the pin can tell them apart
    batch = f"f32[{WW},{FW},{BW},3]"

    def rows_handed(lines):
        return {int(r) for ln in lines for r in re.findall(r"/rows(\d+)", ln)}

    def slot_writes(lines):
        return [ln for ln in lines
                if "dynamic-update-slice(" in ln and f"= {batch}" in ln]

    hist = _scoped(wave_pair["ragged"], "grow/hist")
    assert hist and rows_handed(hist) == set(ladder), rows_handed(hist)
    assert not slot_writes(hist)
    gathered = {int(dims.split(",")[0]) for ln in hist
                for dt, dims in _parse_shapes(ln)
                if dt == "u8" and dims.endswith(f",{FW}")}
    # (the interpreted kernel also reads its own row block, u8[blk, F])
    assert set(ladder) <= gathered and not gathered & buckets, sorted(gathered)
    # the loop that stays for onehot / segment is what the pin would catch
    assert slot_writes(_scoped(wave_pair["unfused"], "grow/hist"))


def test_fused_wave_program_holds_nothing_of_the_unfused_wave(wave_pair):
    """The fused program never reaches the changed branch: no operation of
    it sits under ``grow/hist``, and the rows its launches are handed are
    still the steps of the ladder in KERNEL blocks (the same text as
    before ISSUE 34; ``tools/tpu_aot.py --grower higgs`` prints the
    compiled TPU module's digest to compare two checkouts by)."""
    from lightgbm_tpu.ops.pallas_wave import wave_layout

    fused = wave_pair["fused"]
    assert not _scoped(fused, "grow/hist")
    blk = wave_layout(FW, BW, "f32")["rows_block"]
    ladder = G._wave_row_ladder(WW * blk, (NW // (2 * blk) + WW) * blk, blk)
    handed = {int(r) for ln in _scoped(fused, "grow/wave_gather")
              for r in re.findall(r"/rows(\d+)", ln)}
    assert handed == set(ladder), sorted(handed)


@pytest.mark.parametrize("mode", ["fused", "unfused"])
def test_partition_is_one_ragged_pass_per_wave(wave_pair, mode):
    """ISSUE-30 structural pin: the compiled program partitions a wave in
    ONE pass over its W segments packed back to back — one scatter per
    step of the total-row ladder, straight into ``perm`` — and nothing
    under ``grow/partition`` is shaped by a power-of-two bucket any more
    (the old form: per split leaf a ``switch`` over buckets of 2 048 and
    4 096 rows here, each with its own gather, two cumsums, a scatter into
    ``zeros(S)`` and a ``dynamic-update-slice`` back into ``perm``)."""
    blk = G._partition_block(NW)
    ladder = G._wave_row_ladder(blk, (NW // blk + WW) * blk, blk)
    buckets = set(G._split_buckets(NW))
    assert not buckets & set(ladder)            # the pin can tell them apart
    part = [ln for ln in wave_pair[mode].splitlines()
            if "grow/partition" in ln]
    assert part
    handed = {int(r) for ln in part for r in re.findall(r"/rows(\d+)", ln)}
    assert handed == set(ladder), sorted(handed)
    scatters = [ln for ln in part if re.search(r" scatter\(", ln)]
    assert len(scatters) == len(ladder)
    assert all(f"s32[{2 * NW}]" in ln and "unique_indices=true" in ln
               for ln in scatters)               # into perm itself
    assert not any("dynamic-update-slice(" in ln for ln in part)
    dims = {int(d) for ln in part for _, ds in _parse_shapes(ln)
            for d in ds.split(",") if d}
    assert not dims & buckets, sorted(dims & buckets)


def test_program_flops_bounded(hlo):
    """XLA's own FLOP count for the bench-shaped program (while bodies
    counted once) must stay near the one-hot contraction's analytic cost.
    The round-2 M-packed multi-sibling kernel was a ~100x FLOP
    pessimization on an op that was never FLOP-limited — this pins that
    class of regression without hardware.

    Analytic floor: per wave step the W sibling histograms contract
    (N, F*B) one-hots against (N, 3) values -> ~2*N*F*B*3 FLOPs at the
    static bucket bound, plus split-scan/partition smallness."""
    flops = hlo["fp32_cost"].get("flops", 0.0)
    onehot_step = 2.0 * N * F * B * 3
    assert 0 < flops <= 3.0 * onehot_step, (
        f"program flops {flops:.3e} vs one-hot step {onehot_step:.3e}")
