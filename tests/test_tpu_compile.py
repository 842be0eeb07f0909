"""Ahead-of-time compiles for a described TPU v5e, without a chip.

The TPU compiler is installed with jax; it compiles for a topology that
is described and not attached.  These tests compile the Pallas kernels
of the training round at the shapes of the paper's FEMNIST CNN at its
published width (``femnist_cnn(width=32)``, cut 2: pooled features
[T, 7*7*64]; cut 3: [T, 2048] into the 2048 -> 62 head), and the Engine
round itself on a 4-chip mesh.  A compile that passes runs nothing: it
only shows that Mosaic and XLA accept the program (tiling, VMEM,
partitioning).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("T,D,M,dtype", [
    (512, 3136, 64, jnp.float32),       # cut-2 pooled features
    (640, 3136, 32, jnp.float32),       # 20 clients x 32, server batch 32
    (640, 1, 32, jnp.int32),            # the pooled labels, [T] -> [T, 1]
    (300, 7, 37, jnp.bfloat16),         # a 16-bit source travels as words
], ids=["f32-cut2", "f32-engine", "labels", "bf16"])
def test_feature_resample_compiles_for_v5e(one_chip, T, D, M, dtype):
    from repro.kernels.feature_resample import feature_resample
    text = _compiled_text(
        lambda s, i: feature_resample(s, i, interpret=False),
        _spec(one_chip, (T, D), dtype), _spec(one_chip, (M,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bias", [False, True])
def test_gather_loss_compiles_for_v5e(one_chip, bias):
    from repro.kernels.gather_loss import gather_loss_microbatch
    args = [_spec(one_chip, (512, 2048)), _spec(one_chip, (512,), jnp.int32),
            _spec(one_chip, (64,), jnp.int32), _spec(one_chip, (2048, 62))]
    if bias:
        args.append(_spec(one_chip, (62,)))
    text = _compiled_text(
        lambda *a: gather_loss_microbatch(*a, interpret=False), *args)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,vmapped", [
    ((3136, 2048), False),              # server fc at cut 2
    ((62,), False),                     # a head-sized leaf
    ((5, 5, 1, 32), False),             # client conv 1
    ((20, 5, 5, 32, 64), True),         # client conv 2, cohort-vmapped
], ids=["fc", "small", "conv1", "vmapped-conv2"])
def test_fused_adam_compiles_for_v5e(one_chip, shape, vmapped):
    from repro.kernels.fused_adam import fused_adam
    step = jax.ShapeDtypeStruct(shape[:1] if vmapped else (), jnp.int32,
                                sharding=one_chip)

    def fn(p, g, m, v, t):
        return fused_adam(p, g, m, v, t, lr=1e-3, interpret=False)

    text = _compiled_text(jax.vmap(fn) if vmapped else fn,
                          *[_spec(one_chip, shape)] * 4, step)
    assert "tpu_custom_call" in text


def test_engine_round_compiles_on_4_chip_v5e_mesh(topo, monkeypatch):
    """The Engine's cyclesfl round at width 32 on a (4, 1) mesh of the
    described chips, with the TPU kernel paths on: every Pallas call
    must sit inside a shard_map, since XLA cannot partition one."""
    import repro.api.engine as engine_mod
    from repro.api import Engine, ExperimentConfig
    from repro.launch.mesh import auto_mesh
    from repro.sharding.specs import batch_spec

    # steer the backend gates (kernels compiled, fused Adam) and the
    # Engine's mesh onto the described devices
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(engine_mod, "make_engine_mesh",
                        lambda shape, axes: auto_mesh(shape, axes,
                                                      topo.devices[:4]))
    cfg = ExperimentConfig(algo="cyclesfl", task="image", width=32, cut=2,
                           n_clients=20, attendance=0.2, batch=32, rounds=1,
                           seed=0, mesh_shape=(4, 1))
    eng = Engine(cfg, log=lambda *a, **k: None)
    mesh = eng.mesh
    state = jax.eval_shape(lambda: eng.algo.init(jax.random.PRNGKey(0),
                                                 eng.fed.n_clients))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, eng.state_shardings)
    C = eng.padded_capacity

    def cohort(shape, dtype):
        spec = batch_spec(mesh, shape[0], len(shape) - 1)
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    args = (state, cohort((C,), jnp.int32),
            cohort((C, 32, 28, 28, 1), jnp.float32),
            cohort((C, 32), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32,
                                 sharding=NamedSharding(mesh, P())),
            cohort((C,), jnp.float32))
    text = eng.algo.round.lower(*args).compile().as_text()
    assert C % 4 == 0 and np.prod(mesh.devices.shape) == 4
    assert "tpu_custom_call" in text


def _hlo_computations(text: str) -> dict[str, list[str]]:
    """The compiled module's computations: name -> instruction lines."""
    comps, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            lines = comps[head.group(1)] = []
        elif line == "}":
            lines = None
        elif lines is not None:
            lines.append(line)
    return comps


def _reachable(comps: dict[str, list[str]], root: str) -> set[str]:
    """``root`` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return seen


@pytest.mark.parametrize("algo,task,width,cut,n_clients,img,row", [
    ("cyclepsl", "image", 32, 2, 178, (28, 28, 1), 7 * 7 * 64),
    ("cyclesfl", "cifar", 64, 4, 25, (32, 32, 3), 8 * 8 * 256),
], ids=["femnist_cnn-cut2", "resnet9-cut4"])
def test_inner_loop_body_copies_no_pool(one_chip, monkeypatch, algo, task,
                                        width, cut, n_clients, img, row):
    """The Engine's round on one described v5e: the body of the server
    inner loop's ``while`` holds no copy of the whole pooled store.

    The pool enters the loop in the layout its producer left it
    (N-minor at these shapes), while the resample kernel reads row-major
    [T, prod(row)] rows.  Flattened inside the scan body, that relayout
    ran on every step (the compiler does not hoist it); the inner loop
    now flattens the pool once, before the scan.  Without that hoist
    this test fails: at a cohort of 178 (FEMNIST CNN, cut 2) or 25
    (ResNet9, cut 4) and batch 32 the body copies the [T, D] pool."""
    from repro.api import Engine, ExperimentConfig

    # the kernels' backend gates on; no mesh, as in the benchmark's cells
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ExperimentConfig(algo=algo, task=task, width=width, cut=cut,
                           n_clients=n_clients, attendance=1.0, batch=32,
                           rounds=1, seed=0)
    eng = Engine(cfg, log=lambda *a, **k: None)
    C = eng.padded_capacity
    state = jax.eval_shape(lambda: eng.algo.init(jax.random.PRNGKey(0),
                                                 eng.fed.n_clients))
    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), state)
    args = (state, _spec(one_chip, (C,), jnp.int32),
            _spec(one_chip, (C, 32) + img),
            _spec(one_chip, (C, 32), jnp.int32),
            _spec(one_chip, (2,), jnp.uint32),
            _spec(one_chip, (C,)))
    text = eng.algo.round.lower(*args).compile().as_text()

    comps = _hlo_computations(text)
    bodies = [m.group(1) for lines in comps.values() for line in lines
              if " while(" in line and "ServerUpdate/while" in line
              for m in [re.search(r"body=%([\w.\-]+)", line)]]
    assert C == n_clients and len(bodies) == 1, bodies
    pool = C * 32 * row
    copies = [line.strip()[:160]
              for name in _reachable(comps, bodies[0])
              for line in comps[name]
              for dims in re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", line)
              if np.prod([int(d) for d in dims.split(",") if d]) == pool]
    assert not copies, copies
