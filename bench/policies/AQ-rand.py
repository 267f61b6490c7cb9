"""AQ-rand (Albers and Quedenfeld), randomized and window-free: no peek;
each newly idle level waits Delta_l * log1p(u (e - 1)) slots, the full-span
e/(e - 1) ski-rental draw of its type, with u drawn from the key as the
engine draws it (the value table of the split, at the real level count)
and transformed as the engine transforms it, in float32."""
import numpy as np

from bench.reference import E, uniforms


def horizon(windows, delta):
    return np.zeros(len(windows), np.int64)


def static_wait(windows, delta):
    return np.full(len(windows), float(delta))


def waits(key, n_slots, n_levels, windows, delta):
    """(W, T, N) float32 host table, the same draws for every window;
    ``delta`` a scalar or the per-level ``(N,)`` Delta."""
    import jax
    import jax.numpy as jnp

    _, u = uniforms(key, n_slots, n_levels)
    fn = jax.jit(lambda u, b: b * jnp.log1p(u * (E - 1.0)))
    w = np.asarray(fn(u, jnp.asarray(delta, jnp.float32)))
    return np.broadcast_to(w, (len(windows),) + w.shape)
