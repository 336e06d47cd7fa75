"""``kernel_roofline.serve``: the least time the chip could take for
the conv/deconv passes of the traced window -- each pass's larger bound of
operations over the bf16 peak and least bytes over HBM bandwidth
(``bench/flops.py``) -- over the device time of the ops that did that work
(Pallas calls and XLA convolutions).  Per chip: each chip's own passes over
its own kernel time, averaged."""

import flops as F


def read(name, rd):
    if rd is None or not rd.trace.devices or name.split(".")[1] != rd.kind:
        return None
    kernel_s = rd.kind_s("kernel")
    if kernel_s <= 0 or not rd.passes:
        return None
    pk = rd.peaks
    least = sum(F.min_seconds(l, b, pk["bf16_flops"], pk["hbm_bytes_per_s"])
                for l, _, b in rd.passes)
    return 100.0 * least / kernel_s
