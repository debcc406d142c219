"""Device self time of the ``bucket_views`` scope (the model's tensors
viewed out of the parameter buckets, and their gradients summed back into
a bucket) ÷ traced window, in %, mean over chips
(``scopes.phase_seconds``); nothing without the program's scopes."""


def read(f):
    if f.phases is None:
        return None
    return 100 * f.phases["phases"]["bucket_views"] / f.summary.window_s
