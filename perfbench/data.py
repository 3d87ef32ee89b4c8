"""Seeded on-device data generators: the benchmark's traffic.

A configuration names its generator as ``"module:function"`` (a later PR
adds a module beside this one and names it; nothing here is edited). Every
generator is ``fn(key, n, d, **params) -> (n, d) float32`` in plain
``jax.numpy``; :func:`generate` jits it once for the whole matrix, on the
device, so no row is made on the host.
"""

from __future__ import annotations

import importlib


def seed_key(seed: int):
    """A PRNG key from any non-negative ``--seed`` (the driver's go past
    2**31): the low 31 bits seed the key, the rest are folded in."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def low_rank_rows(key, n: int, d: int, *, top_variances, mean_scale: float = 3.0):
    """Low-rank signal plus unit noise plus a column mean.

    ``x = z + (g * sqrt(top - 1)) @ u.T + mean`` with ``z`` (n, d) and ``g``
    (n, k) standard normal, ``u`` (d, k) a DENSE orthonormal basis and
    ``mean = mean_scale * normal(d)``: the covariance is
    ``I + u diag(top - 1) u.T``, so the leading eigenvalues are
    ``top_variances`` (well separated from each other and from the unit
    bulk), every leading eigenvector spreads over all columns, and centring
    matters. sklearn's ``make_low_rank_matrix`` profile (low-rank signal +
    tail) without its (n, n) QR. The rank-k term is written as k broadcast
    products so XLA fuses it into the pass that writes ``x``.
    """
    import jax
    import jax.numpy as jnp

    top = jnp.asarray(top_variances, dtype=jnp.float32)
    k = top.shape[0]
    kz, kg, ku, km = jax.random.split(key, 4)
    u, _ = jnp.linalg.qr(jax.random.normal(ku, (d, k), dtype=jnp.float32))
    mean = mean_scale * jax.random.normal(km, (d,), dtype=jnp.float32)
    g = jax.random.normal(kg, (n, k), dtype=jnp.float32) * jnp.sqrt(top - 1.0)
    x = jax.random.normal(kz, (n, d), dtype=jnp.float32) + mean
    for j in range(k):
        x = x + g[:, j : j + 1] * u[:, j]
    return x


def blobs(key, n: int, d: int, *, centers: int, cluster_std: float = 1.0,
          center_box=(-10.0, 10.0)):
    """Isotropic Gaussian blobs, sklearn's ``make_blobs`` profile: ``centers``
    blob centres drawn uniformly in ``center_box``, every row one of them
    (drawn at random: the shuffled even split of sklearn, in law) plus
    ``cluster_std`` times unit normal noise. The centre is added as
    ``centers`` broadcast products so XLA fuses it into the pass that writes
    ``x`` (the count is small; a gather would write a second matrix)."""
    import jax
    import jax.numpy as jnp

    kc, kl, kz = jax.random.split(key, 3)
    lo, hi = center_box
    mu = jax.random.uniform(kc, (centers, d), dtype=jnp.float32, minval=lo, maxval=hi)
    label = jax.random.randint(kl, (n,), 0, centers)
    x = cluster_std * jax.random.normal(kz, (n, d), dtype=jnp.float32)
    for j in range(centers):
        x = x + (label == j).astype(jnp.float32)[:, None] * mu[j]
    return x


def resolve(spec: str):
    """``"module:function"`` -> the callable."""
    module, _, name = spec.partition(":")
    if not name:
        raise ValueError(f"expected 'module:function', got {spec!r}")
    return getattr(importlib.import_module(module), name)


def generate(spec: str, seed: int, n: int, d: int, params: dict):
    """Run generator ``spec`` jitted, whole, on the device; blocks until the
    rows exist."""
    import jax

    fn = resolve(spec)
    frozen = {k: tuple(v) if isinstance(v, list) else v for k, v in params.items()}
    jitted = jax.jit(lambda key: fn(key, n, d, **frozen))
    return jax.block_until_ready(jitted(seed_key(seed)))
