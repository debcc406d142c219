"""Time in which a collective runs on a device and no other operation does,
÷ traced window, in %, mean over chips: the part of the ZeRO parameter
all-gather and gradient reduction that compute does not hide."""


def read(f):
    s = f.summary
    if s is None or s.collective_s == 0:
        return None
    return 100 * s.exposed_collective_s / s.window_s
