"""Flagship model builders used by bench.py / __graft_entry__ / tests.

The full reference-parity zoo lives in ``mxtpu.gluon.model_zoo``;
these are the canonical training configurations from BASELINE.md
(LeNet-MNIST is north-star workload 1, ``example/image-classification/
train_mnist.py``†).
"""
from __future__ import annotations

from ..gluon import nn

__all__ = ["lenet", "mlp", "resnet50", "rcnn", "ssd", "transformer",
           "hybrid"]

from . import rcnn  # noqa: E402,F401  (Faster R-CNN family)
from . import ssd  # noqa: E402,F401  (SSD detector family)
from . import transformer  # noqa: E402,F401  (BERT/Transformer family)
from . import hybrid  # noqa: E402,F401  (Mamba-2 / attention decoders)


def resnet50(classes: int = 1000, thumbnail: bool = False):
    """ResNet-50 v1 — north-star workload 2 (BASELINE.md; reference
    ``example/image-classification/symbols/resnet.py``†)."""
    from ..gluon.model_zoo import vision
    return vision.get_resnet(1, 50, thumbnail=thumbnail,
                             classes=classes)


def lenet(classes: int = 10):
    """LeNet-5 as in the reference MNIST example
    (``example/image-classification/symbols/lenet.py``†)."""
    net = nn.HybridSequential(prefix="lenet_")
    net.add(nn.Conv2D(20, kernel_size=5, activation="tanh"),
            nn.MaxPool2D(pool_size=2, strides=2),
            nn.Conv2D(50, kernel_size=5, activation="tanh"),
            nn.MaxPool2D(pool_size=2, strides=2),
            nn.Flatten(),
            nn.Dense(500, activation="tanh"),
            nn.Dense(classes))
    return net


def mlp(classes: int = 10, hidden=(128, 64)):
    """The reference's canonical MLP (``symbols/mlp.py``†)."""
    net = nn.HybridSequential(prefix="mlp_")
    for h in hidden:
        net.add(nn.Dense(h, activation="relu"))
    net.add(nn.Dense(classes))
    return net
