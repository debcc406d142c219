"""Device self time of the ZeRO exchange (the ``grad_reduce`` and
``param_gather`` scopes: the gradient reduction to each chip's shard and
the parameters' all-gather, hidden or not) ÷ traced window, in %, mean
over chips (``scopes.phase_seconds``); nothing without the program's
scopes or without an exchange."""


def read(f):
    if f.phases is None or not f.phases["phases"]["grad_exchange"]:
        return None
    return 100 * f.phases["phases"]["grad_exchange"] / f.summary.window_s
