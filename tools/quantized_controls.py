"""Run the benchmark's ``epsilon-q.train`` with the PROGRAM set to a
control, the comparison unchanged: what ``correct.limits_from`` of
``benchmark/configs/epsilon_q.json`` reads its upper bounds from.

    python3 tools/quantized_controls.py --control two_levels|round_to_nearest \\
        --seed <n> [--seconds 40] [--workload epsilon-q.train]

The Booster gets ``num_grad_quant_bins=2`` or ``stochastic_rounding=false``;
the configuration, and with it ``compare_quantized``, still says 4 levels
and stochastic rounding, so the run must come out not ``correct`` by
``quant_scale_gap`` or ``quant_bias_z`` (the tier-1 test's controls,
``tests/test_quantized_reference.py``, at the cell's size).  The third
control, sums rounded to bfloat16, is a stand-in of the comparison itself:
``benchmark/run.py --control 1``.  TPU only, like the benchmark.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = {"two_levels": {"num_grad_quant_bins": 2},
            "round_to_nearest": {"stochastic_rounding": False}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(CONTROLS), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--workload", default="epsilon-q.train")
    opts = ap.parse_args()
    from benchmark import run
    from benchmark.kinds import train
    plain = train._make_dataset

    def make(lgb, data, params):     # the Booster gets the same dict
        params.update(CONTROLS[opts.control])
        return plain(lgb, data, params)
    train._make_dataset = make
    return run.main(["--workload", opts.workload, "--seed", str(opts.seed),
                     "--seconds", str(opts.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
