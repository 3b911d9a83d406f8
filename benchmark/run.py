"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It looks the cell up in ``BENCHMARK.json``, loads the configuration and the
traffic mix the cell names (``configs/<configuration>.json``,
``traffic/<traffic>.json``) and hands the run to ``kinds/<kind>.py`` for the
traffic file's ``kind``.  No cell, configuration or metric is named in this
file.  The last line of standard output is the result; the numbers that
decided ``correct`` are the last lines of standard error, each beside its
limit.  Without a TPU, or without the program beside it, it exits non-zero
and prints no result.  ``--control 1`` (never passed by the driver) also
reads the control and the faults through the same comparison;
``--data-seed n`` draws other rows than the configuration's (the readings
a limit is set from want more than one data set).
"""

from __future__ import annotations

import time

T0 = time.time()          # set-up is counted from the process's start

import argparse           # noqa: E402
import importlib          # noqa: E402
import json               # noqa: E402
import os                 # noqa: E402
import sys                # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_cell(workload: str, root: str = ROOT, manifest: dict = None) -> dict:
    """The manifest, the cell and its configuration and traffic files."""
    if manifest is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(bench_dir, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {"manifest": manifest, "cell": cell, "config": config,
            "traffic": traffic}


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def _merge(into: dict, part: dict) -> None:
    for key, value in part.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            _merge(into[key], value)
        else:
            into[key] = value


def main(argv=None, require_tpu: bool = True, shrink: dict = None,
         out=None, manifest: dict = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--data-seed", type=int, default=None)
    args = ap.parse_args(argv)

    ctx = load_cell(args.workload, manifest=manifest)   # tests pass their own
    if args.data_seed is not None:     # other rows, for reading limits
        ctx["config"]["data"]["data_seed"] = args.data_seed
    if shrink:                   # the tests' tiny CPU rehearsal, nothing else
        _merge(ctx["config"], shrink)
    ctx.update(root=ROOT, t0=T0 if argv is None else time.time(),
               seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
               control=bool(args.control), require_tpu=require_tpu)
    try:
        importlib.import_module("lightgbm_tpu")
    except ImportError as e:
        sys.stderr.write(f"benchmark: the program is not here ({e}); "
                         "nothing to measure\n")
        return 2
    kind = importlib.import_module(
        "benchmark.kinds." + ctx["traffic"]["kind"])
    result = kind.run(ctx)
    if result is None:
        return 3
    compared = result.pop("compared")
    for name, c in compared.items():
        sys.stderr.write(f"compared {name}: value {c['value']} "
                         f"limit {c['limit']}\n")
    sys.stderr.flush()
    result["compared"] = compared          # last key of the line
    (out or sys.stdout).write(json.dumps(result) + "\n")
    (out or sys.stdout).flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
