"""``idle_share.serve``: the share of time the device ran nothing, 1 -
busy / window, inside the benchmark's ``DcnnServer.step`` spans only, so
that the time spent waiting for arrivals does not count as idle.  Mean over
the chips.
"""


def read(name, rd):
    if rd is None or not rd.trace.devices or name.split(".")[1] != rd.kind:
        return None
    judged = rd.judged_s
    if judged <= 0:
        return None
    return 100.0 * (1.0 - rd.busy_in_windows_s() / judged)
