"""Share of its roofline that the flash attention kernels (forward, dq,
dk/dv) reach in the train step: the least time the chip needs for their
work at true T (the larger of FLOP time and HBM byte time) over their
summed device time in the trace."""

KERNELS = ("_attn_kernel", "_attn_bwd_dq_kernel", "_attn_bwd_dkv_kernel")


def read(o, peak):
    if o.summary is None:
        return None
    t = sum(o.summary.kernel_s.get(k, 0.0) for k in KERNELS)
    if t <= 0.0 or not o.work.get("flash_flops"):
        return None
    need = max(o.work["flash_flops"] / peak["bf16_flops_per_s"],
               o.work["flash_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * need / t
