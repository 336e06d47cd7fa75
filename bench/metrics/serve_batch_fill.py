"""``serve_batch_fill``: real requests over padded batch slots, counted by
the benchmark over every ``step()`` of the traced part of the window."""


def read(name, rd):
    if rd is None or rd.kind != "serve":
        return None
    padded = rd.counters.get("slots_padded", 0)
    if not padded:
        return None
    return 100.0 * rd.counters["slots_real"] / padded
