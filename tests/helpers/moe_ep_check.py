"""Subprocess helper: EP (shard_map) MoE vs dense-dispatch oracle.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8.  Exits 0 if
the EP path matches the dense oracle on an 8-device (data=2, model=4)
mesh, for forward values AND gradients, with generous capacity (so no
tokens are dropped and the two capacity-accounting schemes agree).
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_debug_mesh
from repro.models.moe import moe_ffn, moe_ffn_ep


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_debug_mesh(2, 4)
    B, S, D, E, F, K = 4, 16, 32, 8, 16, 2
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, F, D)) * 0.1, jnp.float32)

    # generous capacity: nothing dropped on either path
    CF = float(E)  # capacity == all tokens

    def dense(x, wg, wu, wd):
        y, aux = moe_ffn(x, router, wg, wu, wd, top_k=K, capacity_factor=CF,
                         num_real=E)
        return y, aux

    def ep(x, wg, wu, wd):
        y, aux = moe_ffn_ep(x, router, wg, wu, wd, top_k=K,
                            capacity_factor=CF, num_real=E, mesh=mesh,
                            dp_axes=("data",), ep_axis="model",
                            fsdp_axis="data")
        return y, aux

    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        wgs = jax.device_put(wg, NamedSharding(mesh, P("model", "data", None)))
        wus = jax.device_put(wu, NamedSharding(mesh, P("model", "data", None)))
        wds = jax.device_put(wd, NamedSharding(mesh, P("model", None, "data")))

        y_ep, aux_ep = jax.jit(ep)(xs, wgs, wus, wds)
        y_dn, aux_dn = jax.jit(dense)(x, wg, wu, wd)

        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dn),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(aux_ep), float(aux_dn), rtol=1e-4)

        # gradients w.r.t. x and all expert weights
        def loss_ep(x, wg, wu, wd):
            y, aux = ep(x, wg, wu, wd)
            return (y ** 2).sum() + aux

        def loss_dn(x, wg, wu, wd):
            y, aux = dense(x, wg, wu, wd)
            return (y ** 2).sum() + aux

        g_ep = jax.jit(jax.grad(loss_ep, argnums=(0, 1, 2, 3)))(
            xs, wgs, wus, wds)
        g_dn = jax.jit(jax.grad(loss_dn, argnums=(0, 1, 2, 3)))(
            x, wg, wu, wd)
        for a, b, name in zip(g_ep, g_dn, ["x", "wg", "wu", "wd"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
                err_msg=f"grad mismatch: {name}")

    # padded-expert path: 8 real out of 12 padded
    E_pad = 12
    router_p = jnp.pad(router, ((0, 0), (0, E_pad - E)))
    wg_p = jnp.pad(wg, ((0, E_pad - E), (0, 0), (0, 0)))
    wu_p = jnp.pad(wu, ((0, E_pad - E), (0, 0), (0, 0)))
    wd_p = jnp.pad(wd, ((0, E_pad - E), (0, 0), (0, 0)))
    with jax.set_mesh(mesh):
        y_pad, aux_pad = jax.jit(
            lambda x: moe_ffn_ep(x, router_p, wg_p, wu_p, wd_p, top_k=K,
                                 capacity_factor=CF, num_real=E, mesh=mesh,
                                 dp_axes=("data",), ep_axis="model",
                                 fsdp_axis=None))(xs)
    np.testing.assert_allclose(np.asarray(y_pad), np.asarray(y_dn),
                               rtol=2e-4, atol=2e-4,
                               err_msg="padded-expert mismatch")
    print("moe_ep_check OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
