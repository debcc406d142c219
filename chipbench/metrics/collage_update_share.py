"""Device time of the ``collage_update`` kernel ÷ traced window, in %,
mean over chips: what an optimizer change can save at most."""


def read(f):
    s = f.summary
    if s is None or not s.kernel_calls.get("collage_update"):
        return None
    return 100 * s.kernel_s["collage_update"] / s.window_s
