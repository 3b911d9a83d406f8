"""Layer ``boosting_loop``: the whole step's share of the chip — 100 x the
least time one chip could take for an iteration's NEEDED work (the larger of
needed ops over peak ops/s and needed bytes over peak HBM bytes/s; bytes
bind for this algorithm) over the traced seconds per iteration."""

from .. import work


def read(trace, facts):
    if not facts["iters"]:
        return None
    lo, hi = facts["window"]
    least, _ = work.least_seconds(facts["needed"]["ops"],
                                  facts["needed"]["bytes"], facts["peak"])
    return 100.0 * least / ((hi - lo) / 1e9 / facts["iters"])
