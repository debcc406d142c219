"""Device self time of the train step's forward pass (the ``forward``
scope, less its recompute inside the backward) ÷ traced window, in %, mean
over chips (``scopes.phase_seconds``); nothing without the program's
scopes."""


def read(f):
    if f.phases is None:
        return None
    return 100 * f.phases["phases"]["forward"] / f.summary.window_s
