"""Federated split-learning training CLI.

Thin flag-parsing front-end over the one driver loop,
``repro.api.Engine``: build an :class:`~repro.api.ExperimentConfig`
from flags (or kwargs via :func:`run`) and call ``Engine.run()``.
A ~100M-param run is just ``--arch`` + width knobs away; the default is
CPU-sized so it finishes in minutes.

Usage:
  PYTHONPATH=src python -m repro.launch.train \
      --algo cyclesfl --task image --rounds 200 --clients 100
"""
from __future__ import annotations

import argparse
import json
import os

from repro.api import Engine, ExperimentConfig
from repro.core.cyclesl import CycleConfig
from repro.utils.compile_cache import enable_compile_cache


def run(algo_name: str, task_name: str = "image", rounds: int = 100,
        n_clients: int = 100, attendance: float = 0.05, batch: int = 16,
        lr_server: float = 1e-3, lr_client: float = 1e-3, alpha: float = 0.5,
        server_epochs: int = 1, seed: int = 0, width: int = 16, cut: int = 2,
        eval_every: int = 20, ckpt_dir: str | None = None, log=print):
    """Kwargs-style wrapper kept for the examples/tests; new code should
    construct an ExperimentConfig and an Engine directly."""
    cfg = ExperimentConfig(
        algo=algo_name, task=task_name, rounds=rounds, n_clients=n_clients,
        attendance=attendance, batch=batch, lr_server=lr_server,
        lr_client=lr_client, alpha=alpha, seed=seed, width=width, cut=cut,
        eval_every=eval_every, ckpt_dir=ckpt_dir,
        cycle=CycleConfig(server_epochs=server_epochs))
    return Engine(cfg, log=log).run()


def main():
    ap = argparse.ArgumentParser()
    ExperimentConfig.add_arguments(ap)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = ExperimentConfig.from_flags(args)
    res = Engine(cfg).run()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    print(json.dumps(res["history"][-1] if res["history"] else {}, indent=1))


if __name__ == "__main__":
    main()
