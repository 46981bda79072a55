"""Device time of the train step's ``fwd_bwd`` scope per optimizer step:
the window's leaf ops in that scope over the trainer's ``trainer.step``
spans that start in it."""


def read(o, peak):
    s = o.summary
    if s is None or "fwd_bwd" not in s.scope_s \
            or not s.span_count.get("trainer.step"):
        return None
    return 1e3 * s.scope_s["fwd_bwd"] / s.span_count["trainer.step"]
