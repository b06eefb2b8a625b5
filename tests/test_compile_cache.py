"""The persistent compile cache is placed from outside, by one rule
(utils/compile_cache.py): ``JAX_COMPILATION_CACHE_DIR`` set → the cache is
there and nothing points it elsewhere; unset → ``<checkout>/.jax_cache``,
derived from the file's own location. A chip call starts on a fresh machine:
unless the directory can be named by the runner and is the same on every
run, every call recompiles the flagship step.

Under test on CPU, each case in a fresh process (the cache directory is read
once per process):

- both branches of the helper, with environment and ``jax.config`` agreeing
  afterwards (so ``obs.metrics.compile_cache_entries`` counts the directory
  in use);
- the hit end to end: two processes compiling the same program against one
  directory named only through the environment variable — only that
  directory gains entries and the second process's backend-compile span
  collapses (deserialization), with the same ``lower()``/``compile()`` split
  the trainer and bench time.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_CHILD = r"""
import json, os, sys, time
sys.path.insert(0, {repo!r})
from hyperscalees_t2i_tpu.utils.compile_cache import place_compile_cache
placed = place_compile_cache()
import jax
import jax.numpy as jnp
from hyperscalees_t2i_tpu.obs.metrics import compile_cache_entries

def prog(x):
    y = x
    for _ in range(12):
        y = jnp.tanh(y @ x) + jax.nn.softmax(y)
    return y

x = jnp.ones((256, 256))
t0 = time.perf_counter()
lowered = jax.jit(prog).lower(x)
t1 = time.perf_counter()
lowered.compile()
t2 = time.perf_counter()
print(json.dumps({{
    "placed": placed,
    "env": os.environ["JAX_COMPILATION_CACHE_DIR"],
    "config": jax.config.jax_compilation_cache_dir,
    "compile_span_s": t2 - t1,
    "entries": compile_cache_entries(),
}}))
"""


def _run(repo: Path, cache_env) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(repo=str(repo))],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_set_only_that_directory_gains_entries_and_second_run_hits(tmp_path):
    cache = tmp_path / "named" / "cc"  # created by the helper
    default = REPO / ".jax_cache"
    count = lambda: len(os.listdir(default)) if default.exists() else 0
    before = count()
    first, second = _run(REPO, cache), _run(REPO, cache)
    assert count() == before  # no code pointed the cache anywhere else
    for r in (first, second):
        assert r["placed"] == r["env"] == r["config"] == str(cache)
    assert first["entries"] > 0, "first run never populated the cache"
    assert second["entries"] >= first["entries"]
    # the contract: the second run DESERIALIZES instead of compiling. The
    # miss side of this program measures ~1s+ on CPU; a hit is ~ms. The
    # bound is generous for shared-runner jitter while still far below any
    # real compile.
    assert second["compile_span_s"] < max(0.25, 0.3 * first["compile_span_s"]), (
        first, second)


def test_env_unset_cache_is_checkout_relative_wherever_the_checkout_lives(tmp_path):
    """Unset → ``<checkout>/.jax_cache`` from the helper file's own location:
    a copy of the package somewhere else caches under THAT copy, never under
    a hard-coded path, a temp name, a pid or a time."""
    elsewhere = tmp_path / "copy"
    shutil.copytree(
        REPO / "hyperscalees_t2i_tpu", elsewhere / "hyperscalees_t2i_tpu",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    r = _run(elsewhere, None)
    want = str(elsewhere / ".jax_cache")
    assert r["placed"] == r["env"] == r["config"] == want
    assert r["entries"] > 0 and os.listdir(want)
    assert _run(elsewhere, None)["placed"] == want  # the same path every run


def test_no_entry_point_hardcodes_a_cache_path():
    """The variable is assigned in exactly one place, and no absolute
    checkout path survives anywhere in the tree."""
    assigning, absolute = [], []
    for path in [REPO / "bench.py", REPO / "chip_smoke.py", REPO / "__graft_entry__.py",
                 *(REPO / "hyperscalees_t2i_tpu").rglob("*.py")]:
        text = path.read_text()
        if '"/root' + "/repo" in text:  # (split: this file must not match itself)
            absolute.append(path.name)
        if ('environ["JAX_COMPILATION_CACHE_DIR"] =' in text
                or 'environ[ENV] =' in text
                or '"jax_compilation_cache_dir"' in text):
            assigning.append(path.name)
    assert absolute == []
    assert assigning == ["compile_cache.py"], assigning
