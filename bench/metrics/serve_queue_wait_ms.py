"""``serve_queue_wait_ms``: mean time a request waited in the server's
queue, from the server's own ``serve_queue_wait_seconds`` sum and count
over the traced part of the window."""


def read(name, rd):
    if rd is None or rd.kind != "serve":
        return None
    n = rd.counters.get("queue_waits", 0)
    if not n:
        return None
    return 1e3 * rd.counters["queue_wait_s"] / n
