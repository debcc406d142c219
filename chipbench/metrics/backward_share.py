"""Device self time of the backward pass (the transposed ``forward``
scope, less the recompute) ÷ traced window, in %, mean over chips
(``scopes.phase_seconds``); nothing without the program's scopes."""


def read(f):
    if f.phases is None:
        return None
    return 100 * f.phases["phases"]["backward"] / f.summary.window_s
