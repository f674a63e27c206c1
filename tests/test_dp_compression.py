"""Data-parallel update with int8 error-feedback gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro import compat

from repro.optim import adam
from repro.optim.dp import make_dp_update


def _mesh():
    return compat.make_mesh((len(jax.devices()),), ("data",))


def _problem():
    target = jnp.arange(8.0) / 4 - 1.0

    def grad_fn(params, batch):
        def loss(p):
            pred = batch @ p["w"]
            return jnp.mean((pred - batch @ target) ** 2)
        return jax.value_and_grad(loss)(params)

    return target, grad_fn


@pytest.mark.parametrize("compression", ["none", "int8"])
def test_dp_update_converges(compression):
    mesh = _mesh()
    target, grad_fn = _problem()
    params = {"w": jnp.zeros(8)}
    opt_init, opt_update = adam(lr=0.05)
    opt_state = opt_init(params)
    error = jax.tree.map(jnp.zeros_like, params)
    update = make_dp_update(grad_fn, opt_update, mesh,
                            compression=compression)
    key = jax.random.PRNGKey(0)
    with jax.sharding.set_mesh(mesh):
        for i in range(300):
            batch = jax.random.normal(jax.random.fold_in(key, i),
                                      (8 * len(jax.devices()), 8))
            params, opt_state, error, loss = update(params, opt_state, error,
                                                    batch)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.05)


def test_compressed_matches_plain_within_tolerance():
    mesh = _mesh()
    target, grad_fn = _problem()
    opt_init, opt_update = adam(lr=0.05)
    outs = {}
    for compression in ("none", "int8"):
        params = {"w": jnp.zeros(8)}
        opt_state = opt_init(params)
        error = jax.tree.map(jnp.zeros_like, params)
        update = make_dp_update(grad_fn, opt_update, mesh,
                                compression=compression)
        key = jax.random.PRNGKey(1)
        with jax.sharding.set_mesh(mesh):
            for i in range(100):
                batch = jax.random.normal(jax.random.fold_in(key, i),
                                          (8 * len(jax.devices()), 8))
                params, opt_state, error, loss = update(
                    params, opt_state, error, batch)
        outs[compression] = np.asarray(params["w"])
    np.testing.assert_allclose(outs["int8"], outs["none"], atol=0.1)
