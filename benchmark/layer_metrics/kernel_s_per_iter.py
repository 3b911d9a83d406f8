"""Layer ``pallas_kernels``: device seconds of the Pallas/Mosaic
custom-call events in the traced window, per boosting iteration."""


def read(trace, facts):
    k = trace.busy_ns(facts["window"], kernels_only=True)
    if not k or not facts["iters"]:
        return None
    return k / 1e9 / facts["iters"]
