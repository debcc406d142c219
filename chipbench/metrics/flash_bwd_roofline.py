"""The flash backward's share of its roofline, in %: ``flash_dq`` and
``flash_dkv`` together, one backward call being one of each; per call the
larger of the backward's operations over the bf16 peak and its bytes over
the HBM peak (``counts/flash``), times the calls, over their device
time."""
from chipbench.counts import flash


def read(f):
    s = f.summary
    if s is None or not s.kernel_calls.get("flash_dq"):
        return None
    calls = s.kernel_calls["flash_dq"]
    dm, L, rows = f.dims, f.traffic["seq_len"], f.traffic["rows_per_chip"]
    least = max(flash.bwd_ops(rows, dm.heads, dm.head_dim, L)
                / f.peaks["bf16_flops_per_s"],
                flash.bwd_bytes(rows, dm.heads, dm.kv_heads, dm.head_dim, L)
                / f.peaks["hbm_bytes_per_s"])
    return 100 * least * calls / (s.kernel_s["flash_dq"]
                                  + s.kernel_s.get("flash_dkv", 0.0))
