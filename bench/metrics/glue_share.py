"""``glue_share.serve``: device time outside conv/deconv work and
collectives (pads, phase split and interleave, concats, crops) over device
busy time, in the traced window."""


def read(name, rd):
    if rd is None or not rd.trace.devices or name.split(".")[1] != rd.kind:
        return None
    busy = rd.busy_s
    if busy <= 0:
        return None
    return 100.0 * rd.kind_s("glue") / busy
