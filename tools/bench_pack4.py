"""Micro-benchmark: 4-bit packed vs byte-per-bin histogram kernel rate.

Run on the real TPU to validate the VERDICT done-criterion "micro-bench >=
the uint8 rate" (the packed kernel streams half the bin bytes, so on an
HBM-bandwidth-bound kernel it should be FASTER, not just equal).

    python tools/bench_pack4.py [rows] [features]
"""

import sys
import time

import numpy as np


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 4_000_000
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.histogram import pack_bins4
    from lightgbm_tpu.ops.pallas_common import interpret_mode
    from lightgbm_tpu.ops.pallas_histogram import histogram_flat

    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, 16, (rows, f)).astype(np.uint8))
    vals = jnp.asarray(rng.randn(rows, 3).astype(np.float32))
    packed = pack_bins4(bins)
    B = 16
    interpret = interpret_mode()

    def rate(fn, reps=10):
        fn().block_until_ready()                  # compile
        t0 = time.time()
        for _ in range(reps):
            out = fn()
        out.block_until_ready()
        return rows * reps / (time.time() - t0)

    r_u8 = rate(lambda: histogram_flat(bins, vals, num_bins=B,
                                       interpret=interpret))
    r_p4 = rate(lambda: histogram_flat(packed, vals, num_bins=B,
                                       packed4=True, features=f,
                                       interpret=interpret))
    print(f"backend={jax.default_backend()} rows={rows} f={f}")
    print(f"uint8  : {r_u8 / 1e9:.3f} G rows/s")
    print(f"packed4: {r_p4 / 1e9:.3f} G rows/s  ({r_p4 / r_u8:.2f}x)")


if __name__ == "__main__":
    main()
