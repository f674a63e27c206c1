"""Paper Table 3: initial compilation time for a population of 20 agents,
Jax (Vectorized) with chained update steps.

Two arms:

  * in-process (default) — one cold XLA compile per algorithm, timed
    directly (the paper's table).
  * ``--restart`` — the persistent-compilation-cache story: the same
    program compiles in two *separate* child processes sharing one cache
    directory (what ``launch/train.py`` / ``launch/serve.py`` do across
    restarts, through ``repro.compat.setup_compilation_cache``).  The
    directory is the fixed ``compile_time_restart`` subdirectory of the
    cache root (``$JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``),
    emptied before the cold arm.  The first child pays the cold compile and
    populates the cache; the second deserializes the executable instead of
    rebuilding it.  Emitted rows are ``arm=cold`` / ``arm=warm`` plus their
    ratio — the restart tax the cache removes.  The parent never touches a
    device, so each child can own the accelerator.

``--json PATH`` dumps all rows in the same artifact style as
``actor_loop`` / ``serve_throughput``.
"""
import argparse
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, td3_batch, write_rows
from repro import compat
from repro.core import population_init, vectorized_update
from repro.rl import td3, sac

OBS, ACT = 17, 6


def _compile_once(mod, n, num_steps) -> float:
    """Seconds for the first (compiling) call of the chained vectorized
    update."""
    key = jax.random.PRNGKey(0)
    pop = population_init(lambda k: mod.init(k, OBS, ACT), key, n)
    batches = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (num_steps,) + x.shape),
        td3_batch(key, n))
    fn = vectorized_update(mod.update, num_steps, donate=False)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(pop, batches, None))
    return time.perf_counter() - t0


def run(n=20, num_steps=10):
    emit(["bench", "agent", "pop", "num_steps", "compile_s"])
    rows = []
    for name, mod in (("td3", td3), ("sac", sac)):
        row = {"bench": "compile_time", "agent": name, "pop": n,
               "num_steps": num_steps,
               "compile_s": round(_compile_once(mod, n, num_steps), 2)}
        rows.append(row)
        emit([row[k] for k in ("bench", "agent", "pop", "num_steps",
                               "compile_s")])
    return rows


# ------------------------------------------------------- restart arm
def _child(n, num_steps):
    """One process lifetime: enable the persistent cache, compile once,
    report the wall time on stdout (the parent parses the sentinel)."""
    compat.setup_compilation_cache()
    print(f"compile_s={_compile_once(td3, n, num_steps):.4f}", flush=True)


def run_restart(n=20, num_steps=10):
    """Cold-vs-warm restart: two child processes, one shared cache dir."""
    cache_dir = str(compat.compilation_cache_dir() / "compile_time_restart")
    shutil.rmtree(cache_dir, ignore_errors=True)
    emit(["bench", "agent", "pop", "num_steps", "arm", "compile_s",
          "warm_over_cold"])
    rows, secs = [], {}
    for arm in ("cold", "warm"):
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.compile_time", "--child",
             "--pop", str(n), "--num-steps", str(num_steps)],
            capture_output=True, text=True, check=True,
            env={**os.environ, "PYTHONPATH": "src",
                 "JAX_COMPILATION_CACHE_DIR": cache_dir},
            cwd=str(compat.REPO_ROOT))
        line = [l for l in out.stdout.splitlines()
                if l.startswith("compile_s=")][-1]
        secs[arm] = float(line.split("=")[1])
        row = {"bench": "compile_time_restart", "agent": "td3",
               "pop": n, "num_steps": num_steps, "arm": arm,
               "compile_s": round(secs[arm], 3),
               "warm_over_cold": round(secs[arm] / secs["cold"], 3)}
        rows.append(row)
        emit([row[k] for k in ("bench", "agent", "pop", "num_steps",
                               "arm", "compile_s", "warm_over_cold")])
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--restart", action="store_true",
                    help="cold-vs-warm compile across process restarts "
                    "sharing a persistent compilation cache")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--fast", action="store_true",
                    help="smaller population / fewer chained steps (CI)")
    ap.add_argument("--pop", type=int, default=None)
    ap.add_argument("--num-steps", type=int, default=None)
    ap.add_argument("--json", default=None, help="also dump rows as JSON")
    args = ap.parse_args()
    n = args.pop or (4 if args.fast else 20)
    num_steps = args.num_steps or (3 if args.fast else 10)
    if args.child:
        _child(n, num_steps)
        sys.exit(0)
    rows = (run_restart(n=n, num_steps=num_steps)
            if args.restart else run(n=n, num_steps=num_steps))
    if args.json:
        write_rows(rows, args.json)
