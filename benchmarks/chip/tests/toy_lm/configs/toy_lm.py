"""Plain reference of a tiny dense decoder, for ``toy_lm-*.json``: a
token configuration that the CPU tests run through the harness, laid out
as a later configuration's files would be (not a cell of the benchmark).

  embed    table[ids] * sqrt(d_model)
  block    x + attn(rms(x)),  then  x + swiglu(rms(x))
  attn     causal softmax attention over ``n_heads`` heads of
           d_model / n_heads, rotary positions (half-split, ``rope_theta``)
           on queries and keys, no biases
  swiglu   (silu(x w_gate) * (x w_up)) w_down
  head     rms(x) w_head, the logits over ``vocab``
  rms      x / sqrt(mean(x^2) + norm_eps) * scale

The cut counts blocks: the client holds the embedding and blocks
``[0, cut)``, the server the other blocks, the final norm and the head.
Weights are a dict, with each block's leaves stacked on a leading layer
axis, in the layout of the program's ``Transformer``.  Inputs are int32
token ids of ``seq_len`` and the labels the next token at each position;
the loss is the mean cross-entropy over every position of every row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def init(key, cfg: dict) -> dict:
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    v = cfg["vocab"]
    k = iter(jax.random.split(key, 9))
    ones = lambda *s: jnp.ones(s, jnp.float32)
    return {
        "embed": {"table": _normal(next(k), (v, d), d)},
        "blocks": {
            "attn": {"wq": _normal(next(k), (L, d, d), d),
                     "wk": _normal(next(k), (L, d, d), d),
                     "wv": _normal(next(k), (L, d, d), d),
                     "wo": _normal(next(k), (L, d, d), d)},
            "ffn": {"w_gate": _normal(next(k), (L, d, f), d),
                    "w_up": _normal(next(k), (L, d, f), d),
                    "w_down": _normal(next(k), (L, f, d), f)},
            "norm_attn": {"scale": ones(L, d)},
            "norm_ffn": {"scale": ones(L, d)}},
        "final_norm": {"scale": ones(d)},
        "lm_head": {"w": _normal(next(k), (d, v), d)},
    }


def split(theta: dict, cut: int):
    blocks = lambda lo, hi: jax.tree.map(lambda a: a[lo:hi], theta["blocks"])
    return ({"embed": theta["embed"], "blocks": blocks(0, cut)},
            {"blocks": blocks(cut, None), "final_norm": theta["final_norm"],
             "lm_head": theta["lm_head"]})


def _rms(scale, x, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, theta):
    """x [B, S, H, Dh]: the first half of each head rotates with the
    second, position s by s * theta^(-2i / Dh)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _block(p, x, cfg, precision):
    mm = lambda a, w: jnp.matmul(a, w.astype(a.dtype), precision=precision)
    b, s, d = x.shape
    h_n = cfg["n_heads"]
    heads = lambda a: a.reshape(b, s, h_n, d // h_n)
    h = _rms(p["norm_attn"]["scale"], x, cfg["norm_eps"])
    q = _rope(heads(mm(h, p["attn"]["wq"])), cfg["rope_theta"])
    k = _rope(heads(mm(h, p["attn"]["wk"])), cfg["rope_theta"])
    v = heads(mm(h, p["attn"]["wv"]))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32), precision=precision)
    logits = logits / np.sqrt(d // h_n)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32),
                   precision=precision).astype(x.dtype)
    x = x + mm(a.reshape(b, s, d), p["attn"]["wo"])
    h = _rms(p["norm_ffn"]["scale"], x, cfg["norm_eps"])
    g = jax.nn.silu(mm(h, p["ffn"]["w_gate"])) * mm(h, p["ffn"]["w_up"])
    return x + mm(g, p["ffn"]["w_down"])


def _blocks(blocks, x, cfg, precision):
    for i in range(jax.tree.leaves(blocks)[0].shape[0]):
        x = _block(jax.tree.map(lambda a: a[i], blocks), x, cfg, precision)
    return x


def client_forward(cp, x, cfg, cut, precision, dtype):
    """Token ids are looked up, not cast; the table is in ``dtype``."""
    table = cp["embed"]["table"].astype(dtype)
    h = jnp.take(table, x, axis=0) * jnp.sqrt(
        jnp.float32(cfg["d_model"])).astype(dtype)
    return _blocks(cp["blocks"], h, cfg, precision)


def server_forward(sp, f, cfg, cut, precision):
    h = _rms(sp["final_norm"]["scale"], _blocks(sp["blocks"], f, cfg,
                                                precision), cfg["norm_eps"])
    return jnp.matmul(h, sp["lm_head"]["w"].astype(h.dtype),
                      precision=precision).astype(jnp.float32)


def loss(logits, y, half: bool):
    ll = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(ll, y[..., None], axis=-1)[..., 0]
    if half:
        nll = nll[: nll.shape[0] // 2]
    return jnp.mean(nll)


def population(cfg: dict, seed: int):
    """``x``, ``y`` ``[N, n, seq_len] int32``: each client walks its own
    successor table over the vocabulary, taking the table's next token
    with probability ``follow`` and a uniform one otherwise; ``y`` is
    ``x`` shifted by one position."""
    pop, v, s = cfg["population"], cfg["vocab"], cfg["seq_len"]
    n_clients, n = pop["n_clients"], pop["samples_per_client"]
    rng = np.random.default_rng(seed)
    succ = np.argsort(rng.random((n_clients, v)), axis=1)
    follow = rng.random((n_clients, n, s)) < pop["follow"]
    other = rng.integers(0, v, (n_clients, n, s))
    seq = np.empty((n_clients, n, s + 1), np.int32)
    seq[..., 0] = rng.integers(0, v, (n_clients, n))
    rows = np.arange(n_clients)[:, None]
    for t in range(s):
        seq[..., t + 1] = np.where(follow[..., t], succ[rows, seq[..., t]],
                                   other[..., t])
    return seq[..., :-1], seq[..., 1:]


def sample_spec(cfg: dict):
    ids = jax.ShapeDtypeStruct((cfg["seq_len"],), jnp.int32)
    return ids, ids


def program_task(cfg: dict, traffic: dict):
    from repro.configs.base import ArchConfig, AttnConfig
    from repro.core.split import make_transformer_task
    return make_transformer_task(ArchConfig(
        name=cfg["name"], family="dense", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_heads"], d_ff=cfg["d_ff"], vocab=cfg["vocab"],
        attn=AttnConfig(rope_theta=cfg["rope_theta"]),
        cut_layers=traffic["cut"], dtype=cfg["dtype"],
        norm_eps=cfg["norm_eps"]))


def tiny(cfg: dict) -> dict:
    return dict(cfg)
