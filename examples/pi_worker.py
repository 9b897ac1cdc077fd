"""SPMD π worker (≙ /root/reference/examples/pi/pi.cc, Python/JAX flavor).

Every worker runs this same program (launcher-less SPMD): rendezvous via the
controller-injected TPUJOB_* env, Monte-Carlo locally, sum across hosts,
host 0 prints. The native C++ flavor is native/examples/pi.cc."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from mpi_operator_tpu.runtime import bootstrap, mesh_from_context


def main():
    ctx = bootstrap.initialize()
    mesh_from_context(ctx)  # sanity: gang and XLA agree on the world

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    key = jax.random.PRNGKey(ctx.host_id)
    pts = jax.random.uniform(key, (n, 2))
    inside = float(jnp.sum(jnp.sum(pts**2, axis=1) < 1.0))

    if ctx.is_distributed:
        from jax.experimental import multihost_utils

        total = float(multihost_utils.process_allgather(jnp.array([inside])).sum())
    else:
        total = inside

    if ctx.is_coordinator:
        pi = 4.0 * total / (n * ctx.num_hosts)
        print(f"pi is approximately {pi:.8f} ({ctx.num_hosts} hosts)")


if __name__ == "__main__":
    main()
