"""Layer ``xla_compile``: seconds the program spent in calls that grew a
jit executable cache (trace + lower + compile, or the load from the
persistent cache), summed over every program: the ``compile.seconds``
histogram that ``telemetry.spans.watch_compiles`` / ``instrument`` feed."""


def read(trace, facts):
    if facts["peak"] is None:
        return None
    from lightgbm_tpu import telemetry
    h = telemetry.registry().snapshot()["histograms"].get("compile.seconds")
    return (h or {}).get("sum") or None
