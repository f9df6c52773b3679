"""conv_roofline.<cells>: the convolutions' share of their roofline: the
least time the chip needs for them (per call the larger of FLOPs over peak
FLOP/s and bytes over peak bandwidth, counted from each convolution's
shapes: input, weights and output once each), over the device time of the
Pallas GEMM kernel (`matmul`) that runs them after im2col, in the traced
slice.  The im2col copies are not in that time: the breakdown shows them
(`concatenate`, `pad`)."""
from benchlib import roofline


def read(name, ctx):
    return roofline.share(ctx, "conv2d", "matmul")
