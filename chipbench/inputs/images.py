"""Synthetic labelled images: standard-normal pixels in the dtype the
model is fed, uniform labels; ((rows, H, W, C), (rows,) int32)."""

import jax
import jax.numpy as jnp


def make(key, config, workload, rows):
    size, channels = config["image_size"], config["num_channels"]
    k_img, k_lab = jax.random.split(key)
    images = jax.random.normal(k_img, (rows, size, size, channels),
                               jnp.dtype(workload["input"]["dtype"]))
    labels = jax.random.randint(k_lab, (rows,), 0, config["num_classes"],
                                jnp.int32)
    return images, labels
