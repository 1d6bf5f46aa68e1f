"""`crdt_tpu.compile_cache`: where the persistent compile cache lands.
Each case runs in a child interpreter so the test process's own JAX
config never has the cache on."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from crdt_tpu.compile_cache import REPO_CACHE_DIR, enable_compile_cache
path = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
print(json.dumps({"path": path, "repo": REPO_CACHE_DIR,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE, mode], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_environment_directory_stays_in_charge(tmp_path):
    got = _probe(tmp_path, "compile")
    assert got["path"] == got["config"] == str(tmp_path)
    assert os.listdir(tmp_path), "nothing was cached in the named dir"


def test_default_is_the_fixed_repo_directory():
    got = _probe(None, "config-only")
    assert got["path"] == got["config"] == got["repo"]
    assert got["repo"] == os.path.join(REPO, ".jax_cache")
