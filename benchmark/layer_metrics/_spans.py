"""Seconds of a host span from the program's own span totals."""


def seconds(area: str, name: str):
    """Summed seconds of the spans whose path ends in ``area/name``;
    ``None`` where the program has no such span."""
    from lightgbm_tpu import telemetry
    s = sum(d["seconds"] for path, d in telemetry.span_totals().items()
            if path.split("/")[-2:] == [area, name])
    return s or None
