"""Layer ``grower``: device seconds per boosting iteration that give the
rows a sampled tree was NOT grown on their leaf — the operations under the
``oob_route`` segment of ``grow/partition`` (the dense per-wave update of
the full-length row -> leaf vector from the go-left bits).  Part of
``partition_s_per_iter``.  ``None`` where trees are grown on every row."""

from . import _segments


def read(trace, facts):
    return _segments.seconds(trace, facts, "oob_route")
