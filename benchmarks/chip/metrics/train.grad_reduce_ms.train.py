"""Device time of the train step's ``grad_reduce`` scope per optimizer step:
the window's leaf ops in that scope over the trainer's ``trainer.step``
spans that start in it."""


def read(o, peak):
    s = o.summary
    if s is None or "grad_reduce" not in s.scope_s \
            or not s.span_count.get("trainer.step"):
        return None
    return 1e3 * s.scope_s["grad_reduce"] / s.span_count["trainer.step"]
