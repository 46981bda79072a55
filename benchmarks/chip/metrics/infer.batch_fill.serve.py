"""Share of the inference tier's padded batch slots that carried a real
request over the window: requests / (requests + padded slots), from the
service's counters."""


def read(o, peak):
    n = o.counters.get("requests", 0.0)
    pad = o.counters.get("padded_slots", 0.0)
    if n + pad <= 0:
        return None
    return 100.0 * n / (n + pad)
