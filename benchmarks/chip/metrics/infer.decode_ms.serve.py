"""Device time of the inference fn's ``decode`` scope per batch: the
window's leaf ops in that scope over the inference worker's
``infer.device`` spans that start in it."""


def read(o, peak):
    s = o.summary
    if s is None or "decode" not in s.scope_s \
            or not s.span_count.get("infer.device"):
        return None
    return 1e3 * s.scope_s["decode"] / s.span_count["infer.device"]
