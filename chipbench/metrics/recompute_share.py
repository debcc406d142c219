"""Device self time of the forward recomputed inside the backward
(``remat``) ÷ traced window, in %, mean over chips
(``scopes.phase_seconds``); nothing without the program's scopes."""


def read(f):
    if f.phases is None:
        return None
    return 100 * f.phases["phases"]["recompute"] / f.summary.window_s
