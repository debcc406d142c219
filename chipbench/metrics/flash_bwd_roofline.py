"""The flash backward's share of its roofline, in %: ``flash_dq`` and
``flash_dkv`` together, one backward call being one of each; per call the
larger of the backward's operations over the bf16 peak and its bytes over
the HBM peak (``counts/flash``, at the widths the cell's family gives),
times the calls, over their device time."""
from chipbench.counts import flash


def read(f):
    s = f.summary
    if s is None or not s.kernel_calls.get("flash_dq"):
        return None
    calls = s.kernel_calls["flash_dq"]
    w = f.family.flash_widths(f.dims)
    L, rows = f.traffic["seq_len"], f.traffic["rows_per_chip"]
    least = max(flash.bwd_ops(rows, w.heads, w.qk_dim, w.v_dim, L)
                / f.peaks["bf16_flops_per_s"],
                flash.bwd_bytes(rows, w.heads, w.kv_heads, w.qk_dim, w.v_dim,
                                L) / f.peaks["hbm_bytes_per_s"])
    return 100 * least * calls / (s.kernel_s["flash_dq"]
                                  + s.kernel_s.get("flash_dkv", 0.0))
