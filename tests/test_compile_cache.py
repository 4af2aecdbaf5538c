"""The persistent compilation cache goes where the environment says, else
to a fixed directory of the checkout that git ignores.  Each case runs in
a fresh process, since the cache location is process-wide state."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
MODULE = Path("src") / "repro" / "launch" / "compile_cache.py"

SCRIPT = """
import importlib.util, sys
import jax, jax.numpy as jnp
spec = importlib.util.spec_from_file_location("compile_cache", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
print("DIR " + mod.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _run(module: Path, cache_env: str | None) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(module)],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    (line,) = [l for l in proc.stdout.splitlines() if l.startswith("DIR ")]
    return line[len("DIR "):]


def test_cache_lands_in_the_env_directory(tmp_path):
    target = tmp_path / "from-env"
    assert _run(ROOT / MODULE, str(target)) == str(target)
    assert any(target.iterdir())


def test_cache_defaults_to_the_checkout_directory(tmp_path):
    # a stand-in checkout, so the test writes nothing into the real one
    module = tmp_path / MODULE
    module.parent.mkdir(parents=True)
    shutil.copy(ROOT / MODULE, module)
    path = _run(module, None)
    assert path == str(tmp_path / ".jax_cache")
    assert any(Path(path).iterdir())
    # the same fixed place on every run, and git ignores it
    assert _run(module, None) == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
