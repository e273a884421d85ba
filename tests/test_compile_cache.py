"""The persistent compilation cache lands where the caller says: in
``JAX_COMPILATION_CACHE_DIR`` when it is set, else in ``.jax_cache/`` at
the root of the checkout.  Each case runs in a fresh interpreter, since
the cache directory is read once per process."""

import os
import subprocess
import sys

from repro.utils.compile_cache import DEFAULT_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.utils.compile_cache import enable_compile_cache
print("CACHE-DIR", enable_compile_cache())
def _cache_probe_{tag}(x):
    return jnp.sin(x) * 3.0
jax.block_until_ready(jax.jit(_cache_probe_{tag})(jnp.ones(3)))
"""


def _run_probe(tmp_path, tag, cache_env):
    script = tmp_path / f"probe_{tag}.py"
    script.write_text(_PROBE.format(src=os.path.join(REPO, "src"), tag=tag))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    proc = subprocess.run([sys.executable, str(script)], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("CACHE-DIR")]
    return line[0].split(" ", 1)[1]


def _entries(directory, tag):
    if not os.path.isdir(directory):
        return []
    return [f for f in os.listdir(directory)
            if f.startswith(f"jit__cache_probe_{tag}-")]


def test_cache_goes_to_the_directory_the_environment_names(tmp_path):
    chosen = tmp_path / "chosen_cache"
    assert _run_probe(tmp_path, "env", chosen) == str(chosen)
    assert _entries(chosen, "env")
    assert not _entries(DEFAULT_CACHE_DIR, "env")


def test_cache_defaults_to_the_checkout_root(tmp_path):
    assert DEFAULT_CACHE_DIR == DEFAULT_CACHE_DIR.parent / ".jax_cache"
    assert os.path.samefile(DEFAULT_CACHE_DIR.parent, REPO)
    assert _run_probe(tmp_path, "default", None) == str(DEFAULT_CACHE_DIR)
    assert _entries(DEFAULT_CACHE_DIR, "default")
