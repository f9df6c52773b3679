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


# ------------------------------------------------------------------- YOLOv4

# yolov4.cfg's anchors (w, h in pixels of the 608 input), largest last; the
# stride-8 head takes the first three.
YOLOV4_ANCHORS = (12, 16, 19, 36, 40, 28, 36, 75, 76, 55, 72, 146, 142, 110,
                  192, 243, 459, 401)


def yolov4_cfg(size: int = 608, width_div: int = 1,
               blocks=(1, 2, 8, 8, 4), classes: int = 80) -> str:
    """The layer sections of darknet's yolov4.cfg (Bochkovskiy, Wang, Liao,
    "YOLOv4: Optimal Speed and Accuracy of Object Detection", 2020) at
    `size`.  Backbone CSPDarknet53, every convolution mish: per stage a
    stride-2 3x3 downsample, a split into two 1x1 branches, `blocks[i]`
    residual blocks (1x1, 3x3, [shortcut]) on one, and a [route] that
    rejoins them.  Neck, leaky: SPP (stride-1 max-pools of 5, 9 and 13
    [route]d with their input) and PANet, a top-down path of [upsample]s
    and [route]s to the stage outputs and a bottom-up path of stride-2 3x3
    convolutions.  Three [yolo] heads at strides 8, 16 and 32, the finest
    first, with scale_x_y 1.2, 1.1 and 1.05.  `width_div` divides every
    width but the heads' 3 * (5 + classes) outputs (tests use a smaller
    cut)."""
    sections = [["[net]", f"height={size}", f"width={size}", "channels=3"]]

    def add(*lines):
        sections.append(list(lines))
        return len(sections) - 2          # the layer's index

    def c(f):
        return f // width_div

    def conv(f, k, stride=1, activation="mish"):
        return add(*_conv(c(f), k, stride, activation))

    conv(32, 3)
    stage_out = []
    for i, n in enumerate(blocks):
        f = 64 << i                        # the stage's output width
        part = f if i == 0 else f // 2     # each branch's width
        down = conv(f, 3, stride=2)
        split = conv(part, 1)
        add("[route]", f"layers = {down - split - 1}")
        conv(part, 1)
        for _ in range(n):
            conv(f // 2, 1)
            conv(part, 3)
            add("[shortcut]", "from=-3", "activation=linear")
        last = conv(part, 1)
        add("[route]", f"layers = -1,{split - last - 1}")
        stage_out.append(conv(f, 1))

    def leaky(f, k, stride=1):
        return conv(f, k, stride, "leaky")

    def five(f):                           # 1x1, 3x3, 1x1, 3x3, 1x1
        for k in (1, 3, 1, 3):
            leaky(f if k == 1 else 2 * f, k)
        return leaky(f, 1)

    leaky(512, 1)
    leaky(1024, 3)
    leaky(512, 1)
    add("[maxpool]", "stride=1", "size=5")          # SPP
    for k, back in ((9, 2), (13, 4)):              # each from the 1x1
        add("[route]", f"layers = -{back}")
        add("[maxpool]", "stride=1", f"size={k}")
    add("[route]", "layers = -1,-3,-5,-6")
    leaky(512, 1)
    leaky(1024, 3)
    top = [leaky(512, 1)]
    for f, lateral in ((256, stage_out[3]), (128, stage_out[2])):
        leaky(f, 1)
        add("[upsample]", "stride=2")
        add("[route]", f"layers = {lateral}")
        leaky(f, 1)
        add("[route]", "layers = -1, -3")
        top.append(five(f))
    anchors = ",  ".join(f"{w},{h}" for w, h in zip(YOLOV4_ANCHORS[::2],
                                                    YOLOV4_ANCHORS[1::2]))
    out = 3 * (5 + classes)
    heads = [(128, "0,1,2", 1.2), (256, "3,4,5", 1.1), (512, "6,7,8", 1.05)]
    for h, (f, mask, scale) in enumerate(heads):
        if h:
            add("[route]", "layers = -4")
            here = leaky(f, 3, stride=2)
            add("[route]", f"layers = -1, {top[-1 - h] - here - 1}")
            five(f)
        leaky(2 * f, 3)
        add(*_conv(out, 1, activation="linear", bn=False))
        add("[yolo]", f"mask = {mask}", f"anchors = {anchors}",
            f"classes={classes}", "num=9", "jitter=.3",
            "ignore_thresh = .7", "truth_thresh = 1", f"scale_x_y = {scale}",
            "iou_thresh=0.213", "cls_normalizer=1.0", "iou_normalizer=0.07",
            "iou_loss=ciou", "nms_kind=greedynms", "beta_nms=0.6",
            "max_delta=5")
    return "\n" + "\n\n".join("\n".join(s) for s in sections) + "\n"


# YOLOv4-608 at its published widths: 162 layers (110 convolutions: 72
# mish, 35 leaky, 3 linear; 23 shortcuts, 21 routes, 3 max-pools, 2
# upsamples, 3 [yolo] heads at 76, 38 and 19).
YOLOV4_CFG = yolov4_cfg()

# The same layer pattern at a CPU test's size: one residual block per
# stage, widths / 8 (so that some mish convolutions have the 32 input
# channels of the implicit-patch kernel), 256x256 input (heads at 32, 16,
# 8, so that the SPP's 5 and 9 windows do not cover the 8-wide grid), 2
# classes.
YOLOV4_SMALL_CFG = yolov4_cfg(size=256, width_div=8, blocks=(1, 1, 1, 1, 1),
                              classes=2)
