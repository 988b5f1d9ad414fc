"""Parameter initializers (jax.nn.initializers wrappers + extras)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def normal(stddev: float = 0.02):
    def init(key, shape, dtype=jnp.float32):
        return stddev * jax.random.normal(key, shape, dtype)

    return init


def zeros(key, shape, dtype=jnp.float32):
    del key
    return jnp.zeros(shape, dtype)


def ones(key, shape, dtype=jnp.float32):
    del key
    return jnp.ones(shape, dtype)


def lecun_normal():
    return jax.nn.initializers.lecun_normal()


def xavier_uniform():
    return jax.nn.initializers.glorot_uniform()


def scaled_normal(fan_in: int):
    """1/sqrt(fan_in) normal — standard transformer projection init.  The
    scale is a Python float, so the result keeps ``dtype`` (a NumPy scalar
    would promote bf16 weights to f32)."""

    def init(key, shape, dtype=jnp.float32):
        return jax.random.normal(key, shape, dtype) / float(np.sqrt(fan_in))

    return init


def random_orthogonal(key, d: int, dtype=jnp.float32):
    """A d x d random orthogonal matrix (QR of a Gaussian).

    Used by the DataMUX "Ortho" multiplexing transform (paper Sec 3.1).
    """
    g = jax.random.normal(key, (d, d), jnp.float32)
    q, r = jnp.linalg.qr(g)
    # Sign-fix so the distribution is Haar-uniform.
    q = q * jnp.sign(jnp.diagonal(r))[None, :]
    return q.astype(dtype)


def random_orthonormal_rows(key, n_rows: int, d: int, dtype=jnp.float32):
    """n_rows <= d orthonormal row vectors in R^d."""
    q = random_orthogonal(key, d, dtype)
    return q[:n_rows]
