"""Layer ``xla_compile``: the compile cost where jax pays it — the sum over
the process of the program's ``jit.trace_seconds``, ``jit.lower_seconds``,
``jit.backend_seconds`` and ``jit.cache_load_seconds`` histograms (fed by
one ``jax.monitoring`` listener: every trace, lowering, backend compile
and load from the persistent cache, whoever jitted it).  ``setup_compile_s``
is the wall time of the CALLS that compiled; the difference is what a first
call costs beyond compiling."""

NAMES = ("jit.trace_seconds", "jit.lower_seconds", "jit.backend_seconds",
         "jit.cache_load_seconds")


def read(trace, facts):
    if facts["peak"] is None:
        return None
    from lightgbm_tpu import telemetry
    hists = telemetry.registry().snapshot()["histograms"]
    return sum(hists[n]["sum"] for n in NAMES if n in hists) or None
