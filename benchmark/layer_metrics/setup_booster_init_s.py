"""Layer ``boosting_loop``: seconds of the program's ``train/booster_init``
spans (``Booster.__init__``'s construction of the GBDT: objective init with
``lambdarank``'s query tables, the initial score, the device copies, the
growth plan) since the process started."""

from ._spans import seconds


def read(trace, facts):
    if facts["peak"] is None:
        return None
    return seconds("train", "booster_init")
