"""Device time of the inference fn's ``prefill`` scope per batch: the
window's leaf ops in that scope over the inference worker's
``infer.device`` spans that start in it."""


def read(o, peak):
    s = o.summary
    if s is None or "prefill" not in s.scope_s \
            or not s.span_count.get("infer.device"):
        return None
    return 1e3 * s.scope_s["prefill"] / s.span_count["infer.device"]
