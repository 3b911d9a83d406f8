"""The work one boosting iteration NEEDS, whatever implements it.

From the shapes and from the grown tree's own counts: with sibling
subtraction, a tree over N rows must histogram the root's N rows and, for
every split, the smaller child — ``rows_hist = N + sum(min(left, right))``.
Each histogrammed row reads F one-byte bins and its gradient and hessian and
adds both into F bins.  Besides that, one pass over the per-row state makes
the gradients and updates the scores.  A lower bound: gathers, the split
scan and histogram write-back are left out, so a true share cannot pass
100 %.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def rows_hist(tree: dict) -> float:
    """``tree`` is one ``dump_model()['tree_info'][k]``."""
    root = tree["tree_structure"]
    if "split_index" not in root:
        return float(root.get("leaf_count", 0))

    def count(node):
        return node["internal_count" if "split_index" in node
                    else "leaf_count"]

    total, stack = float(root["internal_count"]), [root]
    while stack:
        n = stack.pop()
        if "split_index" in n:
            total += min(count(n["left_child"]), count(n["right_child"]))
            stack += [n["left_child"], n["right_child"]]
    return total


def needed(tree: dict, rows: int, features: int, work: dict) -> dict:
    """Needed operations and bytes of the iteration that grew ``tree``:
    ``hist_*`` the histogram part alone, ``ops`` / ``bytes`` the whole."""
    rh = rows_hist(tree)
    hist_bytes = rh * (features * work["bin_bytes"] + 2 * work["grad_bytes"])
    hist_ops = rh * features * 2
    return {"rows_hist": rh,
            "hist_ops": hist_ops, "hist_bytes": hist_bytes,
            "ops": hist_ops + rows * work["grad_ops_per_row"],
            "bytes": hist_bytes + rows * work["row_state_bytes"]}


def peaks(device_kind: str, precision: str) -> dict:
    """``{"ops_per_s", "bytes_per_s"}`` of one chip of this kind.  A device
    that is not in the table is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} "
                       f"(benchmark/peaks.json has "
                       f"{sorted(table['devices'])})")
    dev = table["devices"][device_kind]
    return {"ops_per_s": dev[table["precision_peak"][precision]],
            "bytes_per_s": dev["hbm_bytes_per_s"]}


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple:
    """``(seconds, which)``: the least time the chip could take, and whether
    ``ops`` or ``bytes`` binds."""
    t_ops, t_bytes = ops / peak["ops_per_s"], nbytes / peak["bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
