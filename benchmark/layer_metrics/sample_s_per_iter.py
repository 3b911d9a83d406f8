"""Layer ``boosting_loop``: device seconds per boosting iteration of the
row sampler's selection — the operations under the ``sample`` segment of
``boost/gradients`` (GOSS: the two ``top_k``, the weights, the sort of the
in-bag row ids).  Part of ``boost_s_per_iter``.  ``None`` where nothing
samples on the device."""

from . import _segments


def read(trace, facts):
    return _segments.seconds(trace, facts, "sample")
