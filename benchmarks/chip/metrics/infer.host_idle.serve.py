"""Share of the traced window in which the device was idle while the
inference worker did its host work around a batch: device idle under the
union of its ``infer.collect``, ``infer.swap``, ``infer.prepare`` and
``infer.resolve`` spans, over the window."""


def read(o, peak):
    if o.summary is None or o.summary.serve_host_idle_s is None:
        return None
    return 100.0 * o.summary.serve_host_idle_s / o.summary.window_s
