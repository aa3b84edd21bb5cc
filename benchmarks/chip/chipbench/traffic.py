"""Procedural device populations, made on the device from a seed.

The recipe is the repo's ``digits`` task (``repro.data.synthetic``),
copied here so the yardstick cannot move with the program: every class
is a smooth random prototype (a coarse 7x7 Gaussian grid upsampled to
28x28), and every sample is its class prototype plus pixel noise, rolled
by a small random shift and squashed to (0, 1).  One set of prototypes
is shared by the whole population and the test set, so the task is IID
across devices; each device draws its own labels, noise and shifts.

Devices are generated in chunks under ``lax.map``, so a pool of
gigabytes never holds more than one chunk of temporaries beside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _prototypes(key, num_classes: int, side: int):
    coarse = jax.random.normal(key, (num_classes, 7, 7))
    up = jax.image.resize(coarse, (num_classes, side, side), "bilinear")
    return up / jnp.max(jnp.abs(up), axis=(1, 2), keepdims=True)


def _samples(key, protos, n: int, noise: float, max_shift: int):
    ky, kn, ks = jax.random.split(key, 3)
    num_classes, side, _ = protos.shape
    y = jax.random.randint(ky, (n,), 0, num_classes)
    img = protos[y] + jax.random.normal(kn, (n, side, side)) * noise
    shifts = jax.random.randint(ks, (n, 2), -max_shift, max_shift + 1)

    def roll(im, sh):
        return jnp.roll(jnp.roll(im, sh[0], axis=0), sh[1], axis=1)

    x = jax.nn.sigmoid(2.0 * jax.vmap(roll)(img, shifts))
    return x[..., None].astype(jnp.float32), y.astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def population(key, num_devices: int, per_device: int, n_test: int,
               num_classes: int, side: int, noise: float, max_shift: int):
    """``(dev_x (D, n, side, side, 1), dev_y (D, n), test_x, test_y)``."""
    kp, kd, kt = jax.random.split(key, 3)
    protos = _prototypes(kp, num_classes, side)
    one = functools.partial(_samples, protos=protos, n=per_device,
                            noise=noise, max_shift=max_shift)
    dev_x, dev_y = jax.lax.map(one, jax.random.split(kd, num_devices),
                               batch_size=min(num_devices, 128))
    test_x, test_y = _samples(kt, protos, n_test, noise, max_shift)
    return dev_x, dev_y, test_x, test_y


def make(key, config: dict, traffic: dict):
    """The cell's population from its configuration and traffic files."""
    side = int(config["input_shape"][0])
    return population(key, int(config["num_devices"]),
                      int(config["samples_per_device"]),
                      int(config["test_samples"]),
                      int(config["num_classes"]), side,
                      float(traffic["noise"]), int(traffic["max_shift"]))
