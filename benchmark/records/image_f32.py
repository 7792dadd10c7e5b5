"""Decoded float32 images with a label, what ``examples/imagenet_resnet50.py``
stages by default (``stage_synthetic('imagenet')``: float32 records of
variance one half) and what its ``--data-url`` path ends in after
``normalize``: 4 bytes a value to stack, copy and move to the device."""

import numpy as np

ROW_KEY = "image"
STD = 0.5 ** 0.5


def make(spec, model, rng):
    hw = model["image_size"]
    image = rng.standard_normal((hw, hw, 3), dtype=np.float32)
    image *= STD
    return {"image": image,
            "label": np.int32(rng.integers(model["num_classes"]))}
