#!/usr/bin/env python
"""Train CIFAR-10 (reference `example/image-classification/train_cifar10.py`).

Same harness as train_imagenet.py at 32x32: ResNet-20-ish depth via the
model-zoo builders, synthetic fallback with --benchmark 1.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import data, fit
from symbols import zoo

if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="train cifar10",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    data.add_data_aug_args(parser)
    parser.set_defaults(
        network="resnet",
        num_layers=18,
        num_classes=10,
        num_examples=50000,
        image_shape="3,32,32",
        batch_size=128,
        num_epochs=300,
        lr_step_epochs="50,100",
    )
    args = parser.parse_args()

    net = zoo.get_symbol(
        network=args.network, num_layers=args.num_layers,
        num_classes=args.num_classes,
        image_shape=tuple(int(x) for x in args.image_shape.split(",")))

    fit.fit(args, net, data.get_rec_iter)
