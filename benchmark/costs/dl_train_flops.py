"""The model FLOPs of one DeepLearning fit and no bytes: with this cost a
roofline is bound by operations, so it reads the share of the chip's peak
bf16 FLOP/s that the training program reached (an MFU)."""

from benchmark.costs import dl_train


def cost(state):
    return dict(dl_train.cost(state), bytes=0)
