"""``bench/flops.py`` against counts made by hand."""

import flops as F


def test_dcgan_layer1_deconv():
    # 4x4x1024 -> 8x8x512, 3x3 stride 2, full extent 9 cropped (0, 1).
    # Per dim, outputs 0..7: even o=2i takes taps 0 and 2 (o=0 only tap 0),
    # odd o takes tap 1: 1 + 3*2 + 4 = 11 (input, tap) pairs.
    l1 = F.Layer("deconv1", "deconv", (4, 4), 1024, 512, (3, 3), (2, 2),
                 ((0, 1), (0, 1)))
    assert l1.out_spatial == (8, 8)
    assert F.valid_macs(l1) == 11 * 11 * 1024 * 512
    assert F.flops(l1, 128) == 2 * 128 * 121 * 1024 * 512
    x, y, w = 4 * 4 * 1024, 8 * 8 * 512, 9 * 1024 * 512
    assert F.bytes_moved(l1, 128) == 2 * (128 * x + 128 * y + w)


def test_vnet_merge4_conv():
    # 128x128x64, 32 -> 16 channels, 3x3x3 stride 1 pad 1: per dim of
    # extent S, 3S taps less the two that fall in padding.
    m4 = F.Layer("merge4", "conv", (128, 128, 64), 32, 16, (3, 3, 3),
                 (1, 1, 1), ((1, 1),) * 3)
    assert m4.out_spatial == (128, 128, 64)
    pairs = (3 * 128 - 2) * (3 * 128 - 2) * (3 * 64 - 2)
    assert F.valid_macs(m4, 4) == 4 * pairs * 32 * 16
    vox = 128 * 128 * 64
    assert F.bytes_moved(m4, 4) == 2 * (4 * vox * 32 + 4 * vox * 16
                                        + 27 * 32 * 16)


def test_strided_conv_and_roofline_bound():
    c = F.Layer("disc1", "conv", (64, 64), 3, 8, (3, 3), (2, 2),
                ((1, 1), (1, 1)))
    assert c.out_spatial == (32, 32)
    # per dim: 32 outputs x 3 taps, less the one tap of output 0 in padding
    assert F.valid_macs(c) == 95 * 95 * 3 * 8
    # a layer this thin is bound by bytes, not by operations
    t = F.min_seconds(c, 128, 197e12, 819e9)
    assert t == F.bytes_moved(c, 128) / 819e9
