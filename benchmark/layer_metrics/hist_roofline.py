"""Layer ``pallas_kernels``: 100 x the least time one chip could take for
the NEEDED histogram work of an iteration (``work.needed``'s ``hist_*``:
bytes bind for this algorithm) over the kernels' seconds per iteration."""

from .. import work
from . import kernel_s_per_iter


def read(trace, facts):
    k = kernel_s_per_iter.read(trace, facts)
    if not k:
        return None
    least, _ = work.least_seconds(facts["needed"]["hist_ops"],
                                  facts["needed"]["hist_bytes"],
                                  facts["peak"])
    return 100.0 * least / k
