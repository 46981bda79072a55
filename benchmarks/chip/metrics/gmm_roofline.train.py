"""Share of its roofline that the grouped-matmul kernels (``_gmm_kernel``
for the forward and input gradient, ``_tgmm_kernel`` for the weight
gradient) reach in the train step: the least time the chip needs for
their work (the larger of FLOP time and HBM byte time) over their summed
device time in the trace. The family's work is counted at the expected
held rows; it is put at the rows the window really handed the kernels,
the step's assignments times the window's measured ``moe_held_frac``
(never at the padded tiles), the weights' bytes unscaled."""

KERNELS = ("_gmm_kernel", "_tgmm_kernel")


def read(o, peak):
    if o.summary is None or not o.work.get("gmm_rows"):
        return None
    t = sum(o.summary.kernel_s.get(k, 0.0) for k in KERNELS)
    frac = o.program.get("moe_held_frac")
    if t <= 0.0 or frac is None:
        return None
    scale = frac * o.work["gmm_assignments"] / o.work["gmm_rows"]
    weight = o.work["gmm_weight_bytes"]
    need = max(o.work["gmm_flops"] * scale / peak["bf16_flops_per_s"],
               ((o.work["gmm_bytes"] - weight) * scale + weight)
               / peak["hbm_bytes_per_s"])
    return 100.0 * need / t
