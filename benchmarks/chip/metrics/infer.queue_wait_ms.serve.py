"""Mean time an answered request waited in the inference tier's queue
before its batch formed, over the window: the change of the tier's
``queue_wait_s`` histogram's sum over the change of its count."""


def read(o, peak):
    n = o.program.get("queue_wait_s.count")
    if not n:
        return None
    return 1e3 * o.program["queue_wait_s.sum"] / n
