"""``mfu.serve``: useful model operations over what the chips could do at
their bf16 peak in the same time: the forward passes of real requests, not
padding, over the time inside the benchmark's ``DcnnServer.step`` spans."""


def read(name, rd):
    if rd is None or not rd.trace.devices or name.split(".")[1] != rd.kind:
        return None
    seconds = rd.judged_s
    if seconds <= 0 or rd.useful_flops <= 0:
        return None
    return 100.0 * rd.useful_flops / (seconds * rd.chips
                                      * rd.peaks["bf16_flops"])
