"""End-to-end behaviour tests for the CycleSL system.

The headline integration test trains the synthetic non-iid federated
task with CycleSFL for a handful of rounds and checks it actually
learns (accuracy well above chance) — the full pipeline: data gen ->
Dirichlet split -> attendance sampling -> split model -> Algorithm 1 ->
per-protocol evaluation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.engine import evaluate
from repro.api.tasks import build_task
from repro.launch.train import run
from repro.core.algorithms import make_algorithm
from repro.core.cyclesl import CycleConfig
from repro.data.federated import sample_cohort
from repro.optim import adam


def test_cyclesfl_learns_end_to_end():
    res = run("cyclesfl", task_name="image", rounds=30, n_clients=40,
              attendance=0.2, eval_every=30, width=8,
              log=lambda *a, **k: None)
    final = res["history"][-1]
    assert final["accuracy"] > 0.25          # 10 classes -> chance 0.1
    assert np.isfinite(final["test_loss"])
    assert "grad_stability" in res


def test_cycle_beats_baseline_on_convergence_speed():
    """Paper Table 14's headline: the cycle variant makes progress much
    earlier than its aggregation-based original."""
    accs = {}
    for algo in ("sflv1", "cyclesfl"):
        res = run(algo, task_name="image", rounds=20, n_clients=40,
                  attendance=0.2, eval_every=10, width=8, seed=1,
                  log=lambda *a, **k: None)
        accs[algo] = res["history"][0]["accuracy"]   # after 10 rounds
    assert accs["cyclesfl"] > accs["sflv1"], accs


def test_regression_task_end_to_end():
    res = run("cyclepsl", task_name="gaze", rounds=40, n_clients=20,
              attendance=0.3, eval_every=10, log=lambda *a, **k: None)
    hist = res["history"]
    assert all(np.isfinite(h["test_loss"]) for h in hist)
    assert hist[-1]["test_loss"] < hist[0]["test_loss"]   # it learns


def test_charlm_task_end_to_end():
    res = run("cyclesfl", task_name="charlm", rounds=8, n_clients=10,
              attendance=0.3, eval_every=8, log=lambda *a, **k: None)
    assert np.isfinite(res["history"][-1]["test_loss"])


def test_per_client_eval_used_for_psl_family():
    task, fed, _ = build_task("image", 20, 0.5, 0, width=4, cut=2)
    algo = make_algorithm("psl", task, adam(1e-3), adam(1e-3), CycleConfig())
    state = algo.init(jax.random.PRNGKey(0), fed.n_clients)
    loss, mets = evaluate(task, state, fed)
    assert np.isfinite(loss) and 0.0 <= mets["accuracy"] <= 1.0


def test_checkpointing_roundtrip_through_driver(tmp_path):
    res = run("cyclesfl", task_name="image", rounds=5, n_clients=10,
              attendance=0.3, eval_every=5, ckpt_dir=str(tmp_path),
              log=lambda *a, **k: None)
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path)) == 5
