"""Layer ``grower``: device seconds per boosting iteration of the layout
work around a histogram that takes SEVERAL kernel launches: the operations
that are not kernels and whose scope path carries a ``chunks<K>`` segment
(``telemetry.kernel_rows`` writes it only when K > 1: 2000 columns are 8
launches of 250) — the column slices of the gathered block, pads,
transposes, and the concatenate + reshape + transpose of the chunks'
results.  They are part of ``gather_s_per_iter`` (``grow/hist``), which on
such a cell reads as row gather + this.  Union of the operations' intervals
in the traced window, mean over the device planes.  ``None`` where no path
carries the segment (a one-launch histogram, or a program from before
PR 31)."""

import re

from .. import scopes
from .. import trace as tracemod

_CHUNKS = re.compile(r"/chunks(\d+)/")


def chunks_of(scope):
    """``K`` of the last ``chunks<K>`` segment of a path, or ``None``."""
    if not scope:
        return None
    hits = _CHUNKS.findall("/" + scope.split(":")[0] + "/")
    return int(hits[-1]) if hits else None


def read(trace, facts):
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
            if chunks_of(scope) and not trace.is_kernel(name)
            and tracemod.short_name(name).split(" ")[-1] not in containers])
    return ns / len(planes) / 1e9 / facts["iters"] if ns > 0 else None
