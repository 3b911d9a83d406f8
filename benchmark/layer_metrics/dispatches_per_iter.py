"""Layer ``entry``: program dispatch spans per boosting iteration in the
traced window (``train/fused_iter``, ``train/pack_dispatch``,
``train/grow_apply``, ``train/raw_grow`` — a nested span's path ends in
one of them)."""


def read(trace, facts):
    lo, hi = facts["window"]
    ends = tuple(trace.rules["dispatch_spans"])
    n = sum(1 for name, s, d in trace.host_spans()
            if lo <= s < hi and name.endswith(ends))
    if not n or not facts["iters"]:
        return None
    return n / facts["iters"]
