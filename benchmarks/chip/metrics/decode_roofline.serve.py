"""Share of its roofline that the decode attention kernel reaches: the
least time the chip needs for the answered requests' decode work over the
valid cache (the larger of FLOP time and HBM byte time) over the kernel's
summed device time in the trace."""

KERNELS = ("_decode_kernel",)


def read(o, peak):
    if o.summary is None:
        return None
    t = sum(o.summary.kernel_s.get(k, 0.0) for k in KERNELS)
    if t <= 0.0 or not o.work.get("decode_flops"):
        return None
    need = max(o.work["decode_flops"] / peak["bf16_flops_per_s"],
               o.work["decode_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * need / t
