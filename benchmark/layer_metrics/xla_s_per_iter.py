"""Layer ``grower``: device seconds per boosting iteration in which an
operation ran that is NOT a Pallas/Mosaic kernel (partition gather and
scatter, subtract, XLA split scan, gradients, score update): the device's
busy time less the kernels' time."""


def read(trace, facts):
    busy = trace.busy_ns(facts["window"])
    if not busy or not facts["iters"]:
        return None
    k = trace.busy_ns(facts["window"], kernels_only=True) or 0.0
    if busy <= k:
        return None
    return (busy - k) / 1e9 / facts["iters"]
