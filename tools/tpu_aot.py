"""Compile the Pallas kernels for a TPU v5e WITHOUT a chip.

``jax.experimental.topologies`` hands out compile-only TPU devices from the
installed libtpu, so ``jit(f).lower(...).compile()`` runs the full XLA TPU
pipeline — Mosaic's layout inference and VMEM allocation included — on a
CPU-only box.  That catches the class of error ``lower(lowering_platforms=
("tpu",))`` cannot see (tests/test_tpu_lowering.py runs both).  It compiles;
it does not execute: whether the compiled kernel computes the right answer
is ``chip_smoke.py``'s job, on the chip.

    python tools/tpu_aot.py   # the three kernels, Higgs shape + MS-LTR's tile

prints one ``[OK]``/``[FAIL]`` line per kernel (with the compiler's message)
and exits non-zero when any failed, or 3 when libtpu offers no topology.

    python tools/tpu_aot.py --grower higgs|msltr|epsilon|msltr-goss|epsilon-q [--phase grow/hist]

compiles the whole GROWER at a benchmark cell's shape (1.5 M x 28 on the
fused wave, 2.27 M x 137 and 400 K x 2000 on the unfused, the last also
quantized; 255 leaves, ``leaf_batch=16``)
and prints the compile's seconds, the temporaries it plans, the module's
instruction count, a digest of its text without metadata and a digest of
the jaxpr it was lowered from (all equal on two checkouts: the same
program, kernels included) and the operations it holds
under one phase scope with the distinct ``rows<R>`` their paths carry (the
instances of a bucket set or of a total-row ladder) — a count and a plan,
never a speed.
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY = "v5e:2x2"        # smallest host libtpu describes; one device used


def tpu_sharding():
    """A single-device sharding on a compile-only v5e device."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    # libtpu reads these at load; without them it logs errors about a
    # missing accelerator type before describing the topology anyway
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)),
                         PartitionSpec())


def compile_for_tpu(fn, *args):
    """AOT-compile ``fn`` at ``args`` (``jax.ShapeDtypeStruct``s carrying
    :func:`tpu_sharding`); returns the compiled executable or raises the
    compiler's error."""
    import jax
    return jax.jit(fn).lower(*args).compile()


def kernel_cases(sharding):
    """(name, fn, args) for the three kernels at the Higgs headline shape:
    28 features, max_bin=255, 255 leaves, leaf_batch=16 — the shapes
    ``chip_smoke.py`` runs — and ``histogram_flat`` at the wide tile of the
    benchmark's unfused cell (MS-LTR's 137 columns, which ``kernel_layout``
    hands to ONE launch)."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.pallas_common import C_PAD
    from lightgbm_tpu.ops.pallas_histogram import (histogram_flat,
                                                   histogram_ragged,
                                                   kernel_layout)
    from lightgbm_tpu.ops.pallas_traverse import fused_class_sums
    from lightgbm_tpu.ops.pallas_wave import (STAT_LANES, fused_wave_call,
                                              wave_layout)
    from lightgbm_tpu.ops.split import SplitConfig

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    f, b, leaves, w, n = 28, 255, 255, 16, 65536
    cases = []
    for dtype, vd in (("f32", jnp.float32), ("int8", jnp.int8)):
        cases.append((
            f"histogram_flat {dtype} B={b}",
            functools.partial(histogram_flat, num_bins=b, dtype=dtype),
            (sds((n, f), jnp.uint8), sds((n, 3), vd))))
    wide = 137
    cases.append((
        f"histogram_flat f32 B={b} F={wide}",
        functools.partial(histogram_flat, num_bins=b, dtype="f32"),
        (sds((n, wide), jnp.uint8), sds((n, 3), jnp.float32))))
    cases.append((
        "histogram_flat f32 packed4 B=15",
        functools.partial(histogram_flat, num_bins=15, dtype="f32",
                          packed4=True, features=f),
        (sds((n, f // 2), jnp.uint8), sds((n, 3), jnp.float32))))
    # the unfused wave's ONE ragged launch a wave (a column chunk): MS-LTR's
    # one launch of 137, Epsilon's eight of 250 in f32 and in int8
    # (epsilon-q), and the two other operand forms at the Higgs width
    for name, cols, nb, dtype, vd, p4 in (
            (f"f32 B={b} F={wide}", wide, b, "f32", jnp.float32, False),
            (f"f32 B={b} F=2000", 2000, b, "f32", jnp.float32, False),
            (f"int8 B={b} F=2000", 2000, b, "int8", jnp.int8, False),
            (f"int8 B={b}", f, b, "int8", jnp.int8, False),
            ("f32 packed4 B=15", f // 2, 15, "f32", jnp.float32, True)):
        blk = kernel_layout(f if p4 else cols, nb, dtype, 0, p4)[0]
        cases.append((
            f"histogram_ragged {name}",
            functools.partial(histogram_ragged, slots=w, num_bins=nb,
                              dtype=dtype, packed4=p4,
                              features=f if p4 else 0),
            (sds((n, cols), jnp.uint8), sds((n, 3), vd),
             (sds((n // blk,), jnp.int32),) * 3)))
    scfg = SplitConfig(min_data_in_leaf=0, min_sum_hessian_in_leaf=100.0,
                       has_nan=True, has_categorical=False,
                       use_sorted_categorical=False, has_monotone=False)
    for dtype, vd in (("f32", jnp.float32), ("int8", jnp.int8)):
        lay = wave_layout(f, b, dtype)
        fb = lay["ftile"] * lay["b_pad"]
        acc = jnp.int32 if dtype == "int8" else jnp.float32
        args = [sds((n, lay["cols_tile"]), jnp.uint8),
                sds((C_PAD, n), vd), sds((w, C_PAD, fb), acc),
                sds((w, 2, STAT_LANES), jnp.float32),
                sds((lay["ftile"], 8), jnp.int32),
                sds((n // lay["rows_block"],), jnp.int32),
                sds((1,), jnp.int32)]
        if dtype == "int8":
            args.append(sds((1, 4), jnp.float32))
        cases.append((
            f"fused_wave_call {dtype}",
            functools.partial(fused_wave_call, num_bins=b, features=f,
                              rows_block=16384, dtype=dtype, scfg=scfg),
            tuple(args)))

    t, m, bb = 8, leaves - 1, 32

    def traverse(sf, sb, dl, ic, cb, lc, rc, lq, bins, nanb):
        pack = dict(split_feature=sf, split_bin=sb, default_left=dl,
                    is_cat=ic, cat_bits=cb, left_child=lc, right_child=rc,
                    leaf_q=lq, num_bins=256, depth=32)
        return fused_class_sums(pack, bins, nanb)

    i16, u8 = jnp.int16, jnp.uint8
    cases.append((
        f"fused_class_sums {t}x{leaves}-leaf int8 pack",
        traverse,
        (sds((t, m), i16), sds((t, m), i16), sds((t, m), u8),
         sds((t, m), u8), sds((t, m, bb), u8), sds((t, m), i16),
         sds((t, m), i16), sds((t, leaves), jnp.int8),
         sds((8192, f), u8), sds((f,), jnp.int32))))
    return cases


# rows, features, wave kernel, in-bag rows of a sampled tree (0: every row),
# quantized training (int8 levels, int32 histograms)
GROWER_SHAPES = {"higgs": (1_500_000, 28, "fused", 0, False),
                 "msltr": (2_270_000, 137, "unfused", 0, False),
                 "epsilon": (400_000, 2000, "unfused", 0, False),
                 "msltr-goss": (2_270_000, 137, "unfused", 454_000, False),
                 "epsilon-q": (400_000, 2000, "unfused", 0, True)}


def grower_case(cell: str, sharding):
    """``(fn, args)``: the jitted grower's body at a benchmark cell's
    shape, planned as a TPU would plan it (the Pallas kernels compiled,
    not interpreted: ``interpret_mode`` asks the live backend, which is
    the CPU here, so this tool answers for it)."""
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu.models.grower as G
    import lightgbm_tpu.ops.pallas_common as pc
    from lightgbm_tpu.ops.split import SplitConfig

    pc.interpret_mode = lambda: False
    n, f, wave, in_bag, quantized = GROWER_SHAPES[cell]
    scfg = SplitConfig(min_data_in_leaf=0, min_sum_hessian_in_leaf=100.0,
                       has_nan=False, has_categorical=False,
                       use_sorted_categorical=False, has_monotone=False)
    grow = G.make_grower(G.GrowerConfig(
        num_leaves=255, num_bins=255, split=scfg, leaf_batch=16,
        histogram_impl="pallas", wave_kernel=wave,
        sampling="goss_device" if in_bag else "none", quantized=quantized))
    assert grow.plan.fused == (wave == "fused"), str(grow.plan)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32, f32 = jnp.int32, jnp.float32
    args = (sds((n, f), jnp.uint8), sds((n,), f32), sds((n,), f32),
            sds((n,), f32), sds((f,), jnp.bool_), sds((f,), i32),
            sds((f,), i32), sds((f,), jnp.bool_), sds((f,), i32))
    if in_bag:      # a sampled tree: the in-bag row ids, the last argument
        args += (None,) * 6 + (sds((in_bag,), i32),)
    if quantized:   # the iteration's rounding key
        args += (None, None, sds((2,), jnp.uint32))
    return grow.raw, args


def phase_census(hlo_text: str, phase: str) -> dict:
    """Operations of a compiled module whose innermost phase scope is
    ``phase`` (a fusion counts once; the instructions inside it do not),
    by opcode, and the distinct ``rows<R>`` their paths carry."""
    import collections
    import re

    ops, rows, comp = collections.Counter(), set(), ""
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        op = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z\-]+)\(", line)
        if ("fused_computation" in comp or "sub_computation" in comp
                or not m or not op or phase not in m.group(1)
                or re.search(r"(grow|boost)/[a-z_]+",
                             m.group(1).split(phase)[-1])
                or op.group(1) in ("get-tuple-element", "bitcast", "tuple",
                                   "constant", "parameter")):
            continue
        ops[op.group(1)] += 1
        rows.update(int(r) for r in re.findall(r"/rows(\d+)", m.group(1)))
    return {"operations": sum(ops.values()), "by_opcode": dict(ops),
            "rows": sorted(rows)}


def module_digest(hlo_text: str, jaxpr_text: str) -> dict:
    """What two checkouts are compared by, to show that a cell a PR does
    not touch runs the parent's program: ``instructions`` and ``digest`` of
    the compiled module's text without its metadata (source lines, scope
    paths, stack frames) and without the Mosaic kernels' serialized bodies
    (MLIR bytecode that carries its call sites' line numbers) — opcode,
    shape, layout and operands of every instruction — and ``traced``, a
    digest of the jaxpr the module was lowered from, which holds every
    kernel's body equation by equation and no source location."""
    import hashlib
    import re

    def sha(text):
        return hashlib.sha1(text.encode()).hexdigest()[:16]

    head, _, comps = hlo_text.partition("\n\nFileNames")
    comps = comps[comps.find("\n\n\n"):]      # past the stack-frame tables
    body = re.sub(r",? ?metadata=\{[^}]*\}", "", head + comps)
    body = re.sub(r'"body":"[^"]*"', '"body":""', body)
    n = len(re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = ", body, re.M))
    return {"instructions": n, "digest": sha(body),
            "traced": sha(re.sub(r"0x[0-9a-f]+", "0x", jaxpr_text))}


def main() -> int:
    import argparse
    import time

    ap = argparse.ArgumentParser()
    ap.add_argument("--grower", choices=sorted(GROWER_SHAPES))
    ap.add_argument("--phase", default="grow/partition")
    opts = ap.parse_args()
    try:
        sharding = tpu_sharding()
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology support
        print(f"tpu_aot: no compile-only TPU topology here: {e!r}"[:300])
        return 3
    if opts.grower:
        fn, args = grower_case(opts.grower, sharding)
        t0 = time.time()
        compiled = compile_for_tpu(fn, *args)
        text = compiled.as_text()
        census = phase_census(text, opts.phase)
        import jax
        digest = module_digest(text, str(jax.make_jaxpr(fn)(*args)))
        print(f"[OK] grower {opts.grower}: compiled in "
              f"{time.time() - t0:.1f} s, temporaries "
              f"{compiled.memory_analysis().temp_size_in_bytes} bytes, "
              f"module {digest}; "
              f"under {opts.phase}: {census}", flush=True)
        return 0
    failed = 0
    for name, fn, args in kernel_cases(sharding):
        try:
            compile_for_tpu(fn, *args)
            print(f"[OK] {name}", flush=True)
        except Exception as e:  # noqa: BLE001 — report every kernel
            failed += 1
            print(f"[FAIL] {name}: {type(e).__name__}: {str(e)[:1500]}",
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
