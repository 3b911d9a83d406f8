"""Layer ``dataset``: seconds of the program's ``data/init`` spans
(``Dataset.__init__``'s conversion of the caller's matrix, a float64 copy
of a dense one) since the process started."""

from ._spans import seconds


def read(trace, facts):
    if facts["peak"] is None:
        return None
    return seconds("data", "init")
