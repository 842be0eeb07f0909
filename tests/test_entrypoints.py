"""Process-level contracts of the entry points.

- The benchmark parents that start one child per device count never
  initialize a JAX backend before the children run: a process that has
  opened an accelerator holds it, and the children could then not.
- The persistent compilation cache lands in ``JAX_COMPILATION_CACHE_DIR``
  when that is set (JAX reads it; nothing is set in code), and otherwise
  at the fixed ``<checkout>/.jax_cache``.

Each case runs in a fresh interpreter, so that neither JAX's backend
state nor its config leaks into (or from) the test worker.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(script: str, *args: str, env_update=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_update or {})
    proc = subprocess.run([sys.executable, "-c", script, *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# replaces the module's per-device-count sweep: records whether the
# parent had initialized a backend when the children would start, and
# returns child records that name a backend the parent could not invent
PARENT = """
import importlib, json, sys
from jax._src import xla_bridge
mod = importlib.import_module(sys.argv[1])
seen = []
def fake_sweep(devices, smoke):
    seen.append(xla_bridge.backends_are_initialized())
    return {str(n): {"backend": "from-child", "devices": n} for n in devices}
mod.device_sweep = fake_sweep
sys.argv = [sys.argv[1], *sys.argv[2:]]
res = mod.main()
print(json.dumps({"seen": seen, "backend": res["backend"],
                  "after": xla_bridge.backends_are_initialized()}))
"""


@pytest.mark.parametrize("module,flags", [
    ("benchmarks.bench_round", ["--sweep-only", "--devices", "1,2"]),
    ("benchmarks.bench_population", ["--devices", "1,2"]),
    ("benchmarks.bench_resilience", ["--devices", "1,2"]),
], ids=["round", "population", "resilience"])
def test_bench_parent_starts_children_before_jax(tmp_path, module, flags):
    rec = _run(PARENT, module, *flags, "--out", str(tmp_path / "out.json"))
    assert rec["seen"] == [False]
    assert rec["after"] is False
    assert rec["backend"] == "from-child"
    assert json.loads((tmp_path / "out.json").read_text())["backend"] == \
        "from-child"


CACHE = """
import json, jax
from repro.utils.compile_cache import enable_compile_cache
got = enable_compile_cache()
print(json.dumps({"returned": got,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def test_compile_cache_defaults_to_fixed_checkout_dir():
    rec = _run(CACHE)
    want = str(ROOT / ".jax_cache")
    assert rec == {"returned": want, "config": want}
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_env_dir_is_left_to_jax(tmp_path):
    d = str(tmp_path / "cache")
    rec = _run(CACHE, env_update={"JAX_COMPILATION_CACHE_DIR": d})
    assert rec == {"returned": d, "config": d}
