"""CIFAR-10 data (``"data": "cifar10"``): the benchmark's own copy of the
clients' samples, synthetic images of CIFAR-10's shape and size (50,000
training images of 32x32x3 in 10 classes) over the primary-class
partition (FedDCT §5.1).

The image code is ``images.py``'s, which draws the training stream of
the program's generator (``make_image_dataset``) draw for draw; this
module adds CIFAR-10's sizes to its ``SPECS``, and a configuration
names the set by its ``"dataset"`` key.
"""

from __future__ import annotations

from chipbench.datasets import images
from chipbench.datasets.images import (build_trainer, check, client_stream,
                                       clients, data_gap, n_samples)

__all__ = ["build_trainer", "check", "client_stream", "clients", "data_gap",
           "n_samples"]

images.SPECS.setdefault("cifar10", dict(hw=(32, 32, 3), n_classes=10,
                                        n_train=50_000))
