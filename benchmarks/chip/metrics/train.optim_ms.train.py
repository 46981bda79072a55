"""Device time of the train step's ``optim_update`` scope per optimizer step:
the window's leaf ops in that scope over the trainer's ``trainer.step``
spans that start in it."""


def read(o, peak):
    s = o.summary
    if s is None or "optim_update" not in s.scope_s \
            or not s.span_count.get("trainer.step"):
        return None
    return 1e3 * s.scope_s["optim_update"] / s.span_count["trainer.step"]
