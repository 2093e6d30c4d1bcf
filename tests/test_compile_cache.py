"""Where the persistent compile cache goes (utils/platform.py): the rule
chip runs depend on.

Each case runs in a child: the rule flips process-wide JAX config, and this
suite must keep the cache OFF for itself (see tests/conftest.py). The child
keeps the ``JAX_PLATFORMS=cpu`` that conftest exports and is TOLD which
backend JAX resolved (``jax.default_backend`` is replaced), so no backend is
initialised and the TPU library is never loaded beside the compile tests.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, sys
sys.path.insert(0, {root!r})
import jax
from glint_word2vec_tpu.utils import platform
jax.default_backend = lambda: {backend!r}
if {unpin!r}:
    jax.config.update("jax_platforms", "")
if {installed!r}:
    platform.__file__ = "/nowhere/site-packages/glint_word2vec_tpu/utils/platform.py"
got = [platform.enable_compile_cache(*a) for a in {calls!r}]
print(json.dumps({{
    "returned": got[0],
    "config_dir": jax.config.jax_compilation_cache_dir,
    "enabled": jax.config.jax_enable_compilation_cache,
    "floor": jax.config.jax_persistent_cache_min_compile_time_secs,
    "metadata_in_key":
        jax.config.jax_compilation_cache_include_metadata_in_key,
}}))
"""


def _child(backend, env_dir=None, unpin=False, installed=False, calls=((),)):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = _CHILD.format(root=ROOT, backend=backend, unpin=unpin,
                         installed=installed, calls=list(calls))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.splitlines()[-1])


FIXED = os.path.join(ROOT, ".jax_cache")  # in the checkout, never temp/pid/time


@pytest.mark.parametrize(
    "backend,env_dir,unpin",
    [("tpu", "/somewhere/else", False), ("tpu", None, False),
     ("cpu", "/somewhere/else", False), ("cpu", None, False),
     # A CPU-only host with nothing pinned resolves to cpu all the same:
     # the backend decides, not the pin.
     ("cpu", None, True)],
    ids=["env-set", "env-unset", "cpu-env-set", "cpu", "cpu-unpinned"],
)
def test_compile_cache_rule(backend, env_dir, unpin):
    got = _child(backend, env_dir, unpin)
    # A hit must never hand back another version's op names (the scopes a
    # profile is split by): where the cache is on, metadata is in its key.
    assert got["metadata_in_key"] is (backend != "cpu")
    if backend == "cpu":
        # Off, whatever the environment says.
        assert got["returned"] is None and got["enabled"] is False
    elif env_dir:
        # JAX honours the variable by itself: that directory and no other.
        assert got["returned"] == env_dir == got["config_dir"]
        assert got["enabled"] is True
    else:
        assert got["returned"] == FIXED == got["config_dir"]
        assert got["enabled"] is True


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"],
                         ids=["off", "env-places-it"])
def test_installed_copy_writes_no_cache_beside_itself(env_dir):
    """An installed package has no checkout: nothing lands in
    site-packages; only the variable places a cache."""
    got = _child("tpu", env_dir, installed=True)
    if env_dir:
        assert got["returned"] == env_dir == got["config_dir"]
    else:
        assert got["returned"] is None and got["enabled"] is False
        assert got["config_dir"] is None


@pytest.mark.parametrize(
    "calls,floor",
    [(((),), 1.0), (((0.0,),), 0.0), (((0.0,), (1.0,)), 0.0)],
    ids=["jax-default", "serving-and-smoke", "never-raised-again"],
)
def test_compile_cache_floor(calls, floor):
    """Only the entry points that ask keep sub-second programs, and a later
    command in the same process (chip_smoke.py -> cli train) keeps that."""
    assert _child("tpu", calls=calls)["floor"] == floor
