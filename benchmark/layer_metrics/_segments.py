"""Seconds per iteration of the operations whose scope path carries a
named SEGMENT below its phase (``.../boost/gradients/sample/...``): the
program's ``telemetry.segment`` scopes, read as path segments the way
``hist_chunk_s_per_iter`` reads ``chunks<K>``, so ``scope_names.json``
stays what it is and the enclosing phase keeps the time."""

from .. import scopes
from .. import trace as tracemod


def has_segment(scope, segment: str) -> bool:
    return bool(scope) and f"/{segment}/" in "/" + scope.split(":")[0] + "/"


def seconds(trace, facts, segment: str):
    """Device seconds per traced iteration under ``segment``: the union of
    the operations' intervals in the traced window (enclosing control flow
    left out), mean over the device planes.  ``None`` where no path
    carries the segment — a program from before the segment existed, or a
    cell whose program never runs it."""
    if facts["peak"] is None or not facts["iters"]:
        return None
    planes = scopes.scoped_ops(trace)
    if not planes:
        return None
    containers = trace.rules["container_opcodes"]
    ns = 0.0
    for events in planes:
        ns += tracemod.union_ns([
            [name, s, d]
            for name, s, d, scope in scopes._clip4(events, facts["window"])
            if has_segment(scope, segment)
            and tracemod.short_name(name).split(" ")[-1] not in containers])
    return ns / len(planes) / 1e9 / facts["iters"] if ns > 0 else None
