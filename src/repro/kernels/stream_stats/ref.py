"""Pure-jnp oracle for the stream_stats kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def stream_stats_ref(x: jax.Array):
    """x (k, N) -> (moments (k,4) [S1..S4], xxt (k,k)), all f32."""
    x = x.astype(jnp.float32)
    x2 = x * x
    mom = jnp.stack([x.sum(1), x2.sum(1), (x2 * x).sum(1), (x2 * x2).sum(1)],
                    axis=1)
    return mom, jnp.matmul(x, x.T, precision=jax.lax.Precision.HIGHEST)
