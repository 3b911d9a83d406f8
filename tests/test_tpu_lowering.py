"""The three Pallas kernels must lower — and compile — for a TPU from this
CPU-only box, with ``interpret=False``, at the Higgs headline shape
(28 features, ``max_bin=255``, 255 leaves, ``leaf_batch=16``), and the flat
histogram kernel at the benchmark's wide tile (MS-LTR's 137 columns in one
launch: the unfused cell's kernel).

Interpret mode runs kernel bodies through plain XLA, so a primitive Pallas
TPU cannot lower (``cumsum`` in the split scan, ``dynamic_slice`` on a
loaded value in the traversal — what ``auto`` selected on a TPU until
ISSUE-21) passes every other CPU test.  This file is the guard that no
chipless PR reintroduces one: stage 1 lowers to the ``tpu_custom_call``
(Pallas -> Mosaic MLIR), stage 2 runs the full XLA TPU compile (Mosaic's
layout inference and VMEM allocation) on compile-only devices from the
installed libtpu (tools/tpu_aot.py).  Neither executes the kernel; right
answers on hardware are ``chip_smoke.py``'s job."""

import os
import subprocess
import sys

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))

import tpu_aot  # noqa: E402


def _cases():
    # a CPU sharding only carries shapes here: stage 1 never compiles
    sharding = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    return tpu_aot.kernel_cases(sharding)


@pytest.mark.parametrize("case", _cases(), ids=lambda c: c[0])
def test_kernel_lowers_for_tpu(case):
    _name, fn, args = case
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_kernels_compile_for_v5e_topology():
    """Full Mosaic compile, in a subprocess (it loads libtpu, which the
    test process must not)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "tpu_aot.py")],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if proc.returncode == 3:
        pytest.skip("no compile-only TPU topology: "
                    + proc.stdout.strip()[-200:])
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith(("[OK]", "[FAIL]"))]
    assert proc.returncode == 0 and lines, proc.stdout + proc.stderr[-2000:]
    assert all(ln.startswith("[OK]") for ln in lines), "\n".join(lines)
    assert len(lines) == len(_cases())
