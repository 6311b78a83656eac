"""DGC sparse-gradient + LocalSGD periodic averaging (reference
dgc_optimizer / localsgd_optimizer semantics)."""
import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.distributed.fleet.comm_opt import (DGCState, LocalSGD,
                                                   dgc_compress, dgc_init)


def mesh_of(n, name="dp"):
    return Mesh(np.array(jax.devices()[:n]), (name,))


def test_dgc_sparsity_and_error_feedback():
    params = {"w": jnp.zeros((100,))}
    st = dgc_init(params)
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=100),
                          jnp.float32)}
    send, st = dgc_compress(g, st, sparsity=0.9, momentum=0.0)
    nz = int((np.asarray(send["w"]) != 0).sum())
    assert nz <= 10 + 1
    # unsent mass is retained for later rounds
    np.testing.assert_allclose(np.asarray(send["w"]) + np.asarray(st.v["w"]),
                               np.asarray(g["w"]), atol=1e-6)
    # a residual eventually ships: accumulate the same grad; total sent +
    # residual always equals total injected
    total_sent = np.asarray(send["w"]).copy()
    for _ in range(5):
        send, st = dgc_compress(g, st, sparsity=0.9, momentum=0.0)
        total_sent += np.asarray(send["w"])
    np.testing.assert_allclose(total_sent + np.asarray(st.v["w"]),
                               6 * np.asarray(g["w"]), atol=1e-4)


def test_dgc_allreduce_over_axis():
    mesh = mesh_of(4)
    g = jnp.stack([jnp.full((8,), float(i)) for i in range(4)])

    def f(gi):
        send, _ = dgc_compress({"w": gi[0]}, dgc_init({"w": gi[0]}),
                               sparsity=0.0, momentum=0.0, axis="dp")
        return send["w"][None]

    out = shard_map(f, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
                    check_vma=False)(g)
    np.testing.assert_allclose(np.asarray(out)[0], np.full(8, 1.5), atol=1e-6)


def test_localsgd_periodic_sync():
    mesh = mesh_of(4)
    sync = LocalSGD(k_steps=2, axis="dp")
    p = jnp.arange(4.0)[:, None] * jnp.ones((1, 3))  # per-replica params

    def run(pi, step):
        return sync.maybe_average({"w": pi[0]}, step)["w"][None]

    f = lambda step: shard_map(
        lambda pi: run(pi, step), mesh=mesh, in_specs=(P("dp"),),
        out_specs=P("dp"), check_vma=False)(p)
    # step not divisible by k: untouched
    np.testing.assert_allclose(np.asarray(f(1)), np.asarray(p))
    # divisible: everyone gets the mean (1.5)
    np.testing.assert_allclose(np.asarray(f(2)), np.full((4, 3), 1.5))
