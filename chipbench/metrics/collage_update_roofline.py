"""``collage_update``'s share of its roofline, in %: the bytes it must move
per step on one chip (``counts/collage_update``, from the state's bucket
shards and field widths) at the HBM peak, over its device time per step.
The update is bound by memory."""


def read(f):
    s = f.summary
    if s is None or not s.kernel_calls.get("collage_update") or not f.steps:
        return None
    least = f.optimizer_bytes * f.steps / f.peaks["hbm_bytes_per_s"]
    return 100 * least / s.kernel_s["collage_update"]
