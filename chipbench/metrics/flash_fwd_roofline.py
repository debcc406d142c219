"""``flash_fwd``'s share of its roofline, in %: per call, the larger of its
operations over the bf16 peak and its bytes over the HBM peak
(``counts/flash``, at the widths the cell's family gives), times the
calls, over its device time."""
from chipbench.counts import flash


def read(f):
    s = f.summary
    calls = s.kernel_calls.get("flash_fwd") if s is not None else 0
    if not calls:
        return None
    w = f.family.flash_widths(f.dims)
    L, rows = f.traffic["seq_len"], f.traffic["rows_per_chip"]
    least = max(flash.fwd_ops(rows, w.heads, w.qk_dim, w.v_dim, L)
                / f.peaks["bf16_flops_per_s"],
                flash.fwd_bytes(rows, w.heads, w.kv_heads, w.qk_dim, w.v_dim,
                                L) / f.peaks["hbm_bytes_per_s"])
    return 100 * least * calls / s.kernel_s["flash_fwd"]
