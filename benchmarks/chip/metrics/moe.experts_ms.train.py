"""Device time of the grouped-matmul kernels (``_gmm_kernel``,
``_tgmm_kernel``: the routed experts' forward, input and weight
gradients) per optimizer step: their summed device time in the window
over the trainer's ``trainer.step`` spans that start in it."""

KERNELS = ("_gmm_kernel", "_tgmm_kernel")


def read(o, peak):
    s = o.summary
    if s is None or not s.span_count.get("trainer.step"):
        return None
    t = sum(s.kernel_s.get(k, 0.0) for k in KERNELS)
    if t <= 0.0:
        return None
    return 1e3 * t / s.span_count["trainer.step"]
