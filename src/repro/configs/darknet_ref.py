"""The paper's own use-case: a Darknet-style CNN.

A darknet-19-flavoured classifier (conv+BN+leaky, maxpool pyramid, global
avgpool head) plus a small encoder-decoder net exercising the
[deconvolutional] path the paper explicitly supports.  These are the
configs used by examples/cnn_inference.py and the CNN benchmarks.
"""

# Reduced-resolution darknet-19-style classifier (28x28x3 -> 10 classes).
DARKNET_SMALL_CFG = """
[net]
height=28
width=28
channels=3

[convolutional]
batch_normalize=1
filters=16
size=3
stride=1
pad=1
activation=leaky

[maxpool]
size=2
stride=2

[convolutional]
batch_normalize=1
filters=32
size=3
stride=1
pad=1
activation=leaky

[maxpool]
size=2
stride=2

[convolutional]
batch_normalize=1
filters=64
size=3
stride=1
pad=1
activation=leaky

[shortcut]
from=-1
activation=linear

[avgpool]

[connected]
output=10
activation=linear

[softmax]
"""

# Darknet-19 (darknet19.cfg, Redmon & Farhadi, "YOLO9000", 2017) at its
# published widths: 19 convolutions, 224x224x3 -> 1000 classes.
DARKNET19_CFG = """
[net]
height=224
width=224
channels=3

[convolutional]
batch_normalize=1
filters=32
size=3
stride=1
pad=1
activation=leaky

[maxpool]
size=2
stride=2

[convolutional]
batch_normalize=1
filters=64
size=3
stride=1
pad=1
activation=leaky

[maxpool]
size=2
stride=2

[convolutional]
batch_normalize=1
filters=128
size=3
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=64
size=1
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=128
size=3
stride=1
pad=1
activation=leaky

[maxpool]
size=2
stride=2

[convolutional]
batch_normalize=1
filters=256
size=3
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=128
size=1
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=256
size=3
stride=1
pad=1
activation=leaky

[maxpool]
size=2
stride=2

[convolutional]
batch_normalize=1
filters=512
size=3
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=256
size=1
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=512
size=3
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=256
size=1
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=512
size=3
stride=1
pad=1
activation=leaky

[maxpool]
size=2
stride=2

[convolutional]
batch_normalize=1
filters=1024
size=3
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=512
size=1
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=1024
size=3
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=512
size=1
stride=1
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=1024
size=3
stride=1
pad=1
activation=leaky

[convolutional]
filters=1000
size=1
stride=1
pad=1
activation=linear

[avgpool]

[softmax]
"""

# Encoder-decoder exercising [deconvolutional] + [route] + [upsample].
SEGNET_SMALL_CFG = """
[net]
height=32
width=32
channels=3

[convolutional]
batch_normalize=1
filters=16
size=3
stride=2
pad=1
activation=leaky

[convolutional]
batch_normalize=1
filters=32
size=3
stride=2
pad=1
activation=leaky

[deconvolutional]
filters=16
size=2
stride=2
pad=0
activation=leaky

[route]
layers=0,2

[upsample]
stride=2

[convolutional]
filters=4
size=1
stride=1
pad=0
activation=linear
"""


# ------------------------------------------------------------------- YOLOv3

# yolov3.cfg's anchors (w, h in pixels of the 416 input), largest last; each
# [yolo] head takes three of them by `mask`, the coarsest grid the largest.
YOLOV3_ANCHORS = (10, 13, 16, 30, 33, 23, 30, 61, 62, 45, 59, 119, 116, 90,
                  156, 198, 373, 326)


def _conv(filters, size, stride=1, activation="leaky", bn=True):
    lines = ["[convolutional]"] + (["batch_normalize=1"] if bn else [])
    return lines + [f"filters={filters}", f"size={size}", f"stride={stride}",
                    "pad=1", f"activation={activation}"]


def yolov3_cfg(size: int = 416, width_div: int = 1,
               blocks=(1, 2, 8, 8, 4), classes: int = 80) -> str:
    """The layer sections of darknet's yolov3.cfg (Redmon & Farhadi,
    "YOLOv3: An Incremental Improvement", 2018) at `size`: the Darknet-53
    backbone (a stride-2 3x3 downsample, then `blocks[i]` residual blocks
    of a 1x1 and a 3x3 convolution and a [shortcut] per stage) and three
    detection heads at strides 32, 16 and 8, the finer two fed by an
    [upsample] of the coarser head's features [route]d together with the
    backbone's stage output.  `width_div` divides every width but the
    heads' 3 * (5 + classes) outputs (tests use a smaller cut)."""
    sections = [["[net]", f"height={size}", f"width={size}", "channels=3"]]

    def add(*lines):
        sections.append(list(lines))
        return len(sections) - 2          # the layer's index

    def c(f):
        return f // width_div

    add(*_conv(c(32), 3))
    stage_out = []
    for i, n in enumerate(blocks):
        add(*_conv(c(64 << i), 3, stride=2))
        for _ in range(n):
            add(*_conv(c(32 << i), 1))
            add(*_conv(c(64 << i), 3))
            last = add("[shortcut]", "from=-3", "activation=linear")
        stage_out.append(last)
    anchors = ",  ".join(f"{w},{h}" for w, h in zip(YOLOV3_ANCHORS[::2],
                                                    YOLOV3_ANCHORS[1::2]))
    out = 3 * (5 + classes)
    for head, (f, mask) in enumerate([(512, "6,7,8"), (256, "3,4,5"),
                                      (128, "0,1,2")]):
        if head:
            add("[route]", "layers = -4")
            add(*_conv(c(f), 1))
            add("[upsample]", "stride=2")
            add("[route]", f"layers = -1, {stage_out[-1 - head]}")
        for _ in range(3):
            add(*_conv(c(f), 1))
            add(*_conv(c(2 * f), 3))
        add(*_conv(out, 1, activation="linear", bn=False))
        add("[yolo]", f"mask = {mask}", f"anchors = {anchors}",
            f"classes={classes}", "num=9", "jitter=.3",
            "ignore_thresh = .7", "truth_thresh = 1", "random=1")
    return "\n" + "\n\n".join("\n".join(s) for s in sections) + "\n"


# YOLOv3-416 at its published widths: 107 layers (75 convolutions, 23
# shortcuts, 4 routes, 2 upsamples, 3 [yolo] heads at 13, 26 and 52).
YOLOV3_CFG = yolov3_cfg()

# The same layer pattern at a CPU test's size: one residual block per
# stage, widths / 16, 64x64 input (heads at 2, 4, 8), 2 classes.
YOLOV3_SMALL_CFG = yolov3_cfg(size=64, width_div=16, blocks=(1, 1, 1, 1, 1),
                              classes=2)
