"""Layer ``dataset``: seconds of the program's own ``data/construct`` spans
(``Dataset.construct``: sampling, bin finding, binning the rows) since the
process started, from the program's span totals."""


def read(trace, facts):
    if facts["peak"] is None:
        return None
    from lightgbm_tpu import telemetry
    s = sum(d["seconds"] for name, d in telemetry.span_totals().items()
            if name.split("/")[-2:] == ["data", "construct"])
    return s or None
