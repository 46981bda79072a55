"""Model FLOP/s of the requests the inference tier answered in the window
(prefill, the decode passes, the heads; padded batch slots not counted)
over the chip's bf16 peak."""


def read(o, peak):
    if not o.work.get("model_flops"):
        return None
    return 100.0 * o.work["model_flops"] / (o.window_s
                                            * peak["bf16_flops_per_s"])
