"""Population sharded over a device mesh + on-device PBT exchange
(core/distributed.py), on an 8-host-device mesh in a subprocess."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_host_mesh
from repro.core.distributed import (population_sharding, shard_population,
                                    population_axes)
from repro.core import population_init, pbt_step, sample_hypers, vectorized_update
from repro.configs.base import HyperSpace, PopulationConfig
from repro.rl import td3

mesh = make_host_mesh(model=1, data=8)
N = 8
key = jax.random.PRNGKey(0)
pop = population_init(lambda k: td3.init(k, 3, 1), key, N)
pop = shard_population(pop, mesh)
sh = population_sharding(pop, mesh)
# leading population axis is sharded over the data axis
leaf_sh = jax.tree.leaves(sh)[0]
assert "data" in str(leaf_sh.spec), leaf_sh.spec

space = HyperSpace(log_uniform=(("actor_lr", 3e-5, 3e-3),))
hypers = sample_hypers(key, space, N)
batch = {
 "obs": jax.random.normal(key, (N, 16, 3)),
 "action": jax.random.uniform(key, (N, 16, 1), minval=-1, maxval=1),
 "reward": jax.random.normal(key, (N, 16)),
 "next_obs": jax.random.normal(key, (N, 16, 3)),
 "done": jnp.zeros((N, 16)),
}
with jax.sharding.set_mesh(mesh):
    update = vectorized_update(td3.update, donate=False)
    pop2, metrics = update(pop, batch, hypers)
    # PBT across the sharded population: the member gathers lower to
    # XLA collectives under jit
    pcfg = PopulationConfig(size=N, exploit_frac=0.25, hyper_space=space)
    fitness = jnp.arange(N, dtype=jnp.float32)
    step = jax.jit(lambda k, p, h, f: pbt_step(k, p, h, f, pcfg))
    pop3, hyp3, parents = step(key, pop2, hypers, fitness)
    lowered = jax.jit(lambda k, p, h, f: pbt_step(k, p, h, f, pcfg)).lower(
        key, pop2, hypers, fitness).compile()
hlo = lowered.as_text()
has_collective = any(c in hlo for c in ("all-gather", "all-reduce",
                                        "collective-permute", "all-to-all"))
print(json.dumps({
    "parents": np.asarray(parents).tolist(),
    "pbt_has_collective": bool(has_collective),
    "critic_loss_finite": bool(np.isfinite(float(metrics["critic_loss"][0]))),
}))
"""


@pytest.mark.slow
def test_population_sharded_update_and_pbt_exchange():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["critic_loss_finite"]
    assert out["pbt_has_collective"], \
        "sharded-population PBT should lower to XLA collectives"
    # worst members (0,1) must take parents from the top-25% (6,7)
    assert all(p in (6, 7) for p in out["parents"][:2])
    assert out["parents"][2:] == [2, 3, 4, 5, 6, 7]
