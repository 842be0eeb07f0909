"""The benchmark's own federated image data, made from the seed.

Every client holds ``samples_per_client`` images at the configuration's
published input shape.  An image is the low-frequency prototype of its
class, plus the low-frequency style of its client (feature skew), plus
white noise; each client's labels follow its own Dirichlet(alpha) class
mix (label skew).  Everything is drawn in bulk with NumPy from one
``default_rng(seed)``: the same seed gives the same population.
"""
from __future__ import annotations

import numpy as np


def _smooth(rng: np.random.Generator, n: int, shape: tuple, grid: int,
            scale: float) -> np.ndarray:
    """``n`` patterns of ``shape`` (H, W, C): a ``grid`` x ``grid``
    standard normal field upsampled by repetition, times ``scale``."""
    h, w, c = shape
    coarse = rng.standard_normal((n, grid, grid, c), dtype=np.float32)
    up = np.repeat(np.repeat(coarse, h // grid, axis=1), w // grid, axis=2)
    return up * np.float32(scale)


def make_population(pop: dict, shape: tuple, n_classes: int, seed: int):
    """Return ``x [N, n, H, W, C] float32`` and ``y [N, n] int32``.

    ``pop`` holds ``n_clients``, ``samples_per_client``, ``alpha``,
    ``grid``, and the RMS of the class signal, the client style and the
    noise: ``signal``, ``style_scale`` and ``noise``.
    """
    rng = np.random.default_rng(seed)
    n_clients, n = pop["n_clients"], pop["samples_per_client"]
    protos = _smooth(rng, n_classes, shape, pop["grid"], pop["signal"])
    styles = _smooth(rng, n_clients, shape, pop["grid"], pop["style_scale"])
    mix = rng.gamma(pop["alpha"], size=(n_clients, n_classes))
    cdf = np.cumsum(mix / mix.sum(axis=1, keepdims=True), axis=1)
    u = rng.random((n_clients, n))
    y = np.minimum((u[:, :, None] > cdf[:, None, :]).sum(-1),
                   n_classes - 1).astype(np.int32)
    x = rng.standard_normal((n_clients, n) + tuple(shape), dtype=np.float32)
    x *= np.float32(pop["noise"])
    x += protos[y]
    x += styles[:, None]
    return x, y


def federated(x: np.ndarray, y: np.ndarray, n_test: int):
    """The program's ``FederatedDataset`` over views of ``x``/``y``:
    each client's last ``n_test`` samples are its test split."""
    from repro.data.federated import ClientData, FederatedDataset
    n_train = x.shape[1] - n_test
    return FederatedDataset([
        ClientData(x[i, :n_train], y[i, :n_train], x[i, n_train:],
                   y[i, n_train:]) for i in range(x.shape[0])])
