"""Device self time of the ``optimizer`` scope (the ``collage_update``
kernel and the work around it) ÷ traced window, in %, mean over chips
(``scopes.phase_seconds``); nothing without the program's scopes."""


def read(f):
    if f.phases is None:
        return None
    return 100 * f.phases["phases"]["optimizer"] / f.summary.window_s
