"""Device self time of the vocabulary head (the ``head`` scope: final
norm, logits and loss, in every phase) ÷ traced window, in %, mean over
chips (``scopes.phase_seconds``); nothing without the program's scopes."""


def read(f):
    if f.phases is None:
        return None
    return 100 * f.phases["head"] / f.summary.window_s
