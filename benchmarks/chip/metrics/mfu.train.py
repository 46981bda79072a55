"""Model FLOP/s of the window's optimizer steps over the chip's bf16 peak
(forward and backward, attention at true T; recomputation not counted)."""


def read(o, peak):
    if not o.work.get("model_flops"):
        return None
    return 100.0 * o.work["model_flops"] / (o.window_s
                                            * peak["bf16_flops_per_s"])
