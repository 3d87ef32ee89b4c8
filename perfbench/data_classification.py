"""Seeded on-device rows WITH labels: the traffic of a classifier's cells.

A generator of ``data.py`` returns one matrix; the one here returns the pair
``(x, y)`` that a supervised ``fit`` takes, and ``data.generate`` jits it
whole like the others (``perfbench.data_classification:classification`` in a
configuration's ``data.generator``). ``drivers/fit_loop.py`` hands what a
generator returns to ``fit`` and to the reference unchanged, so a pair needs
no driver of its own.
"""

from __future__ import annotations

BLOCK_ROWS = 10_000  # rows made at a time: the pieces of a block never exist for all rows


def classification(key, n: int, d: int, *, n_classes: int = 2, n_clusters_per_class: int = 2,
                   class_sep: float = 1.0, flip_y: float = 0.01):
    """sklearn's ``make_classification`` in law: ``(x (n, d) float32, y (n,) int32)``.

    ``d // 3`` informative columns, ``d // 3`` redundant ones, the rest noise
    (what upstream's ``gen_data classification`` asks of it). ``n_classes *
    n_clusters_per_class`` clusters, one to a vertex of the hypercube of side
    ``2 * class_sep`` in the informative columns (a vertex drawn twice is not
    redrawn: at 20 columns or more it does not happen); a row belongs to a
    cluster drawn at random (sklearn's shuffled even split, in law), is
    standard normal there, is mixed by its cluster's own matrix ``A_k``,
    uniform in (-1, 1), and moved to the vertex; its class is the cluster's
    index modulo ``n_classes``. The redundant columns are the informative
    ones times one matrix ``B``, uniform in (-1, 1); the rest are standard
    normal noise. ``flip_y`` of the labels are drawn anew at random. No
    shift, no scale, and the columns are NOT shuffled (sklearn shuffles them;
    a linear model cannot tell).

    Rows are made in blocks of ``BLOCK_ROWS`` written into the one output
    buffer, so the device holds the matrix and a block's pieces, never the
    pieces of all rows (at 500,000 x 3000 those would be a second 6 GB).
    """
    import jax
    import jax.numpy as jnp

    n_inf = n_red = d // 3
    n_noise = d - n_inf - n_red
    if n_inf < 1:
        raise ValueError(f"{d} columns hold no informative one")
    clusters = n_classes * n_clusters_per_class
    step = next(b for b in range(min(n, BLOCK_ROWS), 0, -1) if n % b == 0)
    f32 = jnp.float32

    kv, ka, kb, krows = jax.random.split(key, 4)
    vertices = class_sep * (2.0 * jax.random.bernoulli(kv, 0.5, (clusters, n_inf)).astype(f32) - 1.0)
    mix = jax.random.uniform(ka, (clusters, n_inf, n_inf), dtype=f32, minval=-1.0, maxval=1.0)
    redundant = jax.random.uniform(kb, (n_inf, n_red), dtype=f32, minval=-1.0, maxval=1.0)

    def block(i, carry):
        x, y = carry
        kc, kz, kn, kf, kl = jax.random.split(jax.random.fold_in(krows, i), 5)
        cluster = jax.random.randint(kc, (step,), 0, clusters)
        flip = jax.random.uniform(kf, (step,)) < flip_y
        anew = jax.random.randint(kl, (step,), 0, n_classes)
        label = jnp.where(flip, anew, cluster % n_classes).astype(jnp.int32)
        z = jax.random.normal(kz, (step, n_inf), dtype=f32)
        inf = jnp.zeros((step, n_inf), f32)
        for k in range(clusters):
            mine = (cluster == k).astype(f32)[:, None]
            inf = inf + mine * (jnp.matmul(z, mix[k], precision="highest") + vertices[k])
        parts = [inf, jnp.matmul(inf, redundant, precision="highest"),
                 jax.random.normal(kn, (step, n_noise), dtype=f32)]
        x = jax.lax.dynamic_update_slice_in_dim(x, jnp.concatenate(parts, axis=1), i * step, axis=0)
        y = jax.lax.dynamic_update_slice_in_dim(y, label, i * step, axis=0)
        return x, y

    init = (jnp.zeros((n, d), f32), jnp.zeros((n,), jnp.int32))
    return jax.lax.fori_loop(0, n // step, block, init)
