#!/usr/bin/env python
"""Train ImageNet-1k — the BASELINE.json north-star config
(reference `example/image-classification/train_imagenet.py:1-60`).

The default is ResNet-50 v1 with `--kv-store tpu`: data-parallel over
every visible chip with gradients merged by the XLA allreduce path.
Run hermetically with `--benchmark 1` (synthetic data), or point
--data-train/--data-val at recordio files packed by `tools/im2rec.py`.

Examples:
  # throughput smoke on whatever devices are visible
  python train_imagenet.py --benchmark 1 --num-epochs 1 --max-batches 30

  # bf16 AMP training, 8-way data parallel, checkpointing
  python train_imagenet.py --data-train train.rec --dtype bfloat16 \
      --model-prefix ckpt/resnet50
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import data, fit
from symbols import zoo


def set_imagenet_aug(parser):
    """Standard ImageNet augmentation defaults (reference
    train_imagenet.py set_imagenet_aug)."""
    parser.set_defaults(rgb_mean="123.68,116.779,103.939",
                        rgb_std="58.393,57.12,57.375",
                        random_crop=1, random_mirror=1)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="train imagenet-1k",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    fit.add_fit_args(parser)
    data.add_data_args(parser)
    data.add_data_aug_args(parser)
    parser.set_defaults(
        network="resnet",
        num_layers=50,
        num_classes=1000,
        num_examples=1281167,
        image_shape="3,224,224",
        num_epochs=80,
        lr_step_epochs="30,60",
        dtype="float32",
    )
    args = parser.parse_args()

    net = zoo.get_symbol(
        network=args.network, num_layers=args.num_layers,
        num_classes=args.num_classes,
        image_shape=tuple(int(x) for x in args.image_shape.split(",")))

    fit.fit(args, net, data.get_rec_iter)
