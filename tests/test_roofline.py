"""Calibration tests documenting the roofline methodology (DESIGN/§Roofline).

These pin the two empirical facts the analysis rests on:
  1. cost_analysis() is per-device under SPMD partitioning,
  2. XLA counts while bodies once; our loop-aware HLO model is exact
     on (nested) scan calibration cases.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.roofline import analyze_record, model_flops
from repro.utils.hlo_cost import module_cost


def _flops(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", 0.0))


def test_cost_analysis_counts_scan_body_once():
    def f(x, ws):
        def body(c, w):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, ws)
        return y

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((256, 256), jnp.float32),
                         jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
                         ).compile()
    one = 2 * 256 ** 3
    assert _flops(c) == pytest.approx(one, rel=0.05)          # NOT 10x


def test_loop_aware_cost_counts_trips():
    def f(x, ws):
        def body(c, w):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, ws)
        return y

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((256, 256), jnp.float32),
                         jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
                         ).compile()
    mc = module_cost(c.as_text())
    assert mc.flops == pytest.approx(10 * 2 * 256 ** 3, rel=0.01)


def test_loop_aware_cost_nested_scans():
    def g(x, ws):
        def outer(c, _):
            def inner(ci, w):
                return ci @ w, ()
            c2, _ = jax.lax.scan(inner, c, ws)
            return c2, ()
        y, _ = jax.lax.scan(outer, x, jnp.zeros((3,)))
        return y

    c = jax.jit(g).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32),
                         jax.ShapeDtypeStruct((5, 128, 128), jnp.float32)
                         ).compile()
    mc = module_cost(c.as_text())
    assert mc.flops == pytest.approx(15 * 2 * 128 ** 3, rel=0.01)


def test_loop_aware_plain_dot_exact():
    c = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((128, 64), jnp.float32),
        jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
    assert module_cost(c.as_text()).flops == 2 * 128 * 64 * 32


def test_model_flops_moe_uses_active_params():
    dense = model_flops("phi3-mini-3.8b", "train_4k")
    moe = model_flops("olmoe-1b-7b", "train_4k")
    from repro.configs.registry import get_config
    olmoe = get_config("olmoe-1b-7b")
    assert olmoe.n_active_params() < 0.3 * olmoe.n_params()
    assert moe == pytest.approx(6.0 * olmoe.n_active_params() * 256 * 4096)
    assert dense > 0


def test_analyze_record_terms():
    rec = {
        "status": "ok", "arch": "phi3-mini-3.8b", "shape": "train_4k",
        "n_devices": 256, "device_kind": "TPU v5 lite",
        "loop_aware": {"flops": 1e14, "traffic_bytes": 1e12,
                       "collective_bytes": 5e10},
        "cost": {}, "collectives": {},
    }
    a = analyze_record(rec)
    assert a["t_compute_s"] == pytest.approx(1e14 / 197e12)
    assert a["t_memory_s"] == pytest.approx(1e12 / 819e9)
    assert a["t_collective_s"] == pytest.approx(5e10 / 50e9)
    assert a["dominant"] == "t_memory_s".replace("t_", "").replace("_s", "")


def test_analyze_record_unknown_device_raises():
    """Peaks come from one table keyed by device_kind: a device without
    published peaks is an error, never a default."""
    rec = {"status": "ok", "arch": "phi3-mini-3.8b", "shape": "train_4k",
           "n_devices": 4, "device_kind": "cpu",
           "loop_aware": {"flops": 1.0, "traffic_bytes": 1.0,
                          "collective_bytes": 1.0}}
    with pytest.raises(KeyError, match="no published peaks"):
        analyze_record(rec)
