"""Share of the traced window in which no operation ran on the device, in
%: 1 − (union of the device's op intervals ÷ window), mean over chips."""


def read(f):
    if f.summary is None:
        return None
    return 100 * (1 - f.summary.busy_s / f.summary.window_s)
