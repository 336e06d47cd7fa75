"""Operations and bytes of conv and deconv layers, counted from shapes.

The benchmark's own arithmetic, kept apart from the program so that no
change to the program can change how its work is counted.  A layer is a
``Layer``; ``padding`` holds per-dim ``(lo, hi)`` pairs: the input padding
of a strided convolution, or the border crop of a transposed convolution
(its full output ``(S - 1) * s + K`` cropped by ``lo`` and ``hi``).

Only valid products count: a tap that lands in padding, or outside a
cropped output, multiplies nothing.  The forward, the input gradient (dx)
and the weight gradient (dw) of one layer contract the same triples of
(input, tap, output) positions, so each pass has the same count.  Bytes are
the least a pass must move: its two operands and its result once each, at
the storage width.
"""

from __future__ import annotations

import dataclasses
import math

PASSES = ("fwd", "dx", "dw")


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    op: str                                  # "conv" | "deconv"
    in_spatial: tuple[int, ...]
    cin: int
    cout: int
    kernel: tuple[int, ...]
    stride: tuple[int, ...]
    padding: tuple[tuple[int, int], ...]

    @property
    def out_spatial(self) -> tuple[int, ...]:
        out = []
        for s, k, st, (lo, hi) in zip(self.in_spatial, self.kernel,
                                      self.stride, self.padding):
            if self.op == "conv":
                out.append((s + lo + hi - k) // st + 1)
            else:
                out.append((s - 1) * st + k - lo - hi)
        return tuple(out)


def _valid_pairs_1d(layer: Layer, d: int) -> int:
    """(input index, tap) pairs of one spatial dim that meet an output."""
    s_in, k, st = layer.in_spatial[d], layer.kernel[d], layer.stride[d]
    lo, _ = layer.padding[d]
    s_out = layer.out_spatial[d]
    n = 0
    if layer.op == "conv":
        for o in range(s_out):
            for t in range(k):
                i = o * st + t - lo
                n += 0 <= i < s_in
    else:
        for i in range(s_in):
            for t in range(k):
                o = i * st + t - lo
                n += 0 <= o < s_out
    return n


def valid_macs(layer: Layer, batch: int = 1) -> int:
    """Multiply-accumulates of one pass over ``batch`` samples."""
    per_dim = math.prod(_valid_pairs_1d(layer, d)
                        for d in range(len(layer.in_spatial)))
    return batch * per_dim * layer.cin * layer.cout


def flops(layer: Layer, batch: int = 1) -> int:
    """Operations of one pass (fwd, dx or dw): two per valid MAC."""
    return 2 * valid_macs(layer, batch)


def bytes_moved(layer: Layer, batch: int = 1, width: int = 2) -> int:
    """Least bytes of one pass: x, w and y once each at ``width`` bytes."""
    x = batch * math.prod(layer.in_spatial) * layer.cin
    y = batch * math.prod(layer.out_spatial) * layer.cout
    w = math.prod(layer.kernel) * layer.cin * layer.cout
    return width * (x + y + w)


def min_seconds(layer: Layer, batch: int, peak_flops: float,
                peak_bytes_per_s: float, width: int = 2) -> float:
    """Roofline time of one pass: the larger of its compute and HBM bounds."""
    return max(flops(layer, batch) / peak_flops,
               bytes_moved(layer, batch, width) / peak_bytes_per_s)


def dense_flops(n_in: int, n_out: int, batch: int) -> int:
    return 2 * batch * n_in * n_out
