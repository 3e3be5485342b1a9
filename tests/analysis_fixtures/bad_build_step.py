"""no-replicated-index violation: a shard_map build step whose per-device
body materializes the full ``[n, L]`` index (replicated output spec) —
what a host-driven gather-then-broadcast build would trace."""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def make_replicated_build_step(mesh, n: int, l: int):
    def local_fn(contrib):
        # every device holds (and returns) the whole [n, L] index
        dense = jnp.zeros((n, l), jnp.float32) + jnp.sum(contrib)
        return dense

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P("model", None),),
        out_specs=P(None, None),
        check_vma=False,
    )


def trace(n: int = 64, l: int = 16):
    mesh = jax.make_mesh((1,), ("model",))
    step = make_replicated_build_step(mesh, n, l)
    contrib = jnp.ones((8, 4), jnp.float32)
    return jax.make_jaxpr(step)(contrib)
