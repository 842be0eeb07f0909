"""The configuration interface for a model of stages fed images.

``femnist_cnn.py`` and ``resnet9.py`` define ``init`` and
``apply_range`` over a list of one parameter dict per stage, in the
layout of the program's ``StageModel``; this module gives them the rest
of the interface that the harness calls (PERF.md §4):

  split(theta, cut)                   the stage list at the cut index
  client_forward / server_forward     ``forwards(apply_range, n_stages)``
  loss(logits, y, half)               mean softmax cross-entropy, one
                                      class label per row
  population(cfg, seed)               the federated images below
  sample_spec(cfg)                    one image and its label
  program_task(cfg, traffic)          ``repro.core.split.make_stage_task``
                                      over ``cfg["program_model"]``
  tiny(cfg)                           width 4, 10 classes, 12 clients of 10

The population: every client holds ``samples_per_client`` images at the
configuration's published input shape.  An image is the low-frequency
prototype of its class, plus the low-frequency style of its client
(feature skew), plus white noise; each client's labels follow its own
Dirichlet(alpha) class mix (label skew).  Everything is drawn in bulk
with NumPy from one ``default_rng(seed)``: the same seed gives the same
population.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def split(theta: list, cut: int):
    return theta[:cut], theta[cut:]


def forwards(apply_range, n_stages: int):
    """``(client_forward, server_forward)`` over stages ``[0, cut)`` and
    ``[cut, n_stages)``; the client casts its images to ``dtype``."""

    def client_forward(cp, x, cfg, cut, precision, dtype):
        return apply_range(cp, x.astype(dtype), 0, cut, precision)

    def server_forward(sp, f, cfg, cut, precision):
        return apply_range(sp, f, cut, n_stages, precision)

    return client_forward, server_forward


def loss(logits, y, half: bool):
    ll = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(ll, y[:, None], axis=-1)[:, 0]
    if half:
        nll = nll[: nll.shape[0] // 2]
    return jnp.mean(nll)


def sample_spec(cfg: dict):
    return (jax.ShapeDtypeStruct(tuple(cfg["input_shape"]), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32))


def program_task(cfg: dict, traffic: dict):
    from repro.core.split import make_stage_task
    from repro.models import cnn
    pm = cfg["program_model"]
    return make_stage_task(getattr(cnn, pm["builder"])(**pm["kwargs"]),
                           cut=traffic["cut"], kind="xent")


def tiny(cfg: dict) -> dict:
    pm = cfg["program_model"]
    return dict(cfg, width=4, n_classes=10,
                program_model=dict(pm, kwargs=dict(pm["kwargs"], width=4,
                                                   n_classes=10)),
                samples_per_client=10,
                population=dict(cfg["population"], n_clients=12,
                                test_per_client=1))


def _smooth(rng: np.random.Generator, n: int, shape: tuple, grid: int,
            scale: float) -> np.ndarray:
    """``n`` patterns of ``shape`` (H, W, C): a ``grid`` x ``grid``
    standard normal field upsampled by repetition, times ``scale``."""
    h, w, c = shape
    coarse = rng.standard_normal((n, grid, grid, c), dtype=np.float32)
    up = np.repeat(np.repeat(coarse, h // grid, axis=1), w // grid, axis=2)
    return up * np.float32(scale)


def population(cfg: dict, seed: int):
    """Return ``x [N, n, H, W, C] float32`` and ``y [N, n] int32``.

    ``cfg["population"]`` holds ``n_clients``, ``alpha``, ``grid``, and
    the RMS of the class signal, the client style and the noise:
    ``signal``, ``style_scale`` and ``noise``; ``n`` is
    ``cfg["samples_per_client"]``.
    """
    pop, shape, n_classes = cfg["population"], tuple(cfg["input_shape"]), \
        cfg["n_classes"]
    rng = np.random.default_rng(seed)
    n_clients, n = pop["n_clients"], cfg["samples_per_client"]
    protos = _smooth(rng, n_classes, shape, pop["grid"], pop["signal"])
    styles = _smooth(rng, n_clients, shape, pop["grid"], pop["style_scale"])
    mix = rng.gamma(pop["alpha"], size=(n_clients, n_classes))
    cdf = np.cumsum(mix / mix.sum(axis=1, keepdims=True), axis=1)
    u = rng.random((n_clients, n))
    y = np.minimum((u[:, :, None] > cdf[:, None, :]).sum(-1),
                   n_classes - 1).astype(np.int32)
    x = rng.standard_normal((n_clients, n) + shape, dtype=np.float32)
    x *= np.float32(pop["noise"])
    x += protos[y]
    x += styles[:, None]
    return x, y
