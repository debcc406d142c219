"""``flash_fwd``'s share of its roofline, in %: per call, the larger of its
operations over the bf16 peak and its bytes over the HBM peak
(``counts/flash``), times the calls, over its device time."""
from chipbench.counts import flash


def read(f):
    s = f.summary
    calls = s.kernel_calls.get("flash_fwd") if s is not None else 0
    if not calls:
        return None
    dm, L, rows = f.dims, f.traffic["seq_len"], f.traffic["rows_per_chip"]
    least = max(flash.fwd_ops(rows, dm.heads, dm.head_dim, L)
                / f.peaks["bf16_flops_per_s"],
                flash.fwd_bytes(rows, dm.heads, dm.kv_heads, dm.head_dim, L)
                / f.peaks["hbm_bytes_per_s"])
    return 100 * least * calls / s.kernel_s["flash_fwd"]
