"""Seeded on-device rows WITH real-valued labels: the traffic of a regressor's cells.

Like ``data_classification.py``, the generator here returns the pair ``(x, y)``
that a supervised ``fit`` takes (``perfbench.data_regression:regression`` in a
configuration's ``data.generator``); ``data.generate`` jits it whole and
``drivers/fit_loop.py`` hands the pair to ``fit`` and to the reference unchanged.
"""

from __future__ import annotations

BLOCK_ROWS = 10_000  # rows made at a time: a block's pieces never exist for all rows


def regression(key, n: int, d: int, *, n_informative=None, noise: float = 0.0, bias: float = 0.0):
    """sklearn's ``make_regression`` in law: ``(x (n, d) float32, y (n,) float32)``.

    Standard normal columns (``effective_rank`` None); the first ``n_informative``
    (``d // 3`` where None: what upstream's ``gen_data regression`` asks of it)
    carry coefficients ``100 * U(0, 1)``, the rest nought; ``y = x @ coef + bias +
    noise * normal``. sklearn shuffles rows and columns afterwards; a linear model
    cannot tell, and the generator here does not.

    Rows are made in blocks of ``BLOCK_ROWS`` written into the one output buffer.
    """
    import jax
    import jax.numpy as jnp

    n_inf = d // 3 if n_informative is None else int(n_informative)
    if not 1 <= n_inf <= d:
        raise ValueError(f"{d} columns cannot hold {n_inf} informative ones")
    step = next(b for b in range(min(n, BLOCK_ROWS), 0, -1) if n % b == 0)
    f32 = jnp.float32

    kcoef, krows = jax.random.split(key)
    coef = 100.0 * jax.random.uniform(kcoef, (n_inf,), dtype=f32)

    def block(i, carry):
        x, y = carry
        kx, ke = jax.random.split(jax.random.fold_in(krows, i))
        xb = jax.random.normal(kx, (step, d), dtype=f32)
        yb = jnp.matmul(xb[:, :n_inf], coef, precision="highest") + bias
        yb = yb + noise * jax.random.normal(ke, (step,), dtype=f32)
        x = jax.lax.dynamic_update_slice_in_dim(x, xb, i * step, axis=0)
        y = jax.lax.dynamic_update_slice_in_dim(y, yb.astype(f32), i * step, axis=0)
        return x, y

    init = (jnp.zeros((n, d), f32), jnp.zeros((n,), f32))
    return jax.lax.fori_loop(0, n // step, block, init)
