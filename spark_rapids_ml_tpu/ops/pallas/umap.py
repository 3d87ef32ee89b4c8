"""Pallas TPU kernel: bucketed tail scatter-add for the UMAP layout SGD.

The synchronous UMAP epoch (ops.umap._make_epoch_fn) applies every edge's
attractive gradient twice: once to the head (a DENSE (n, k, dim) sum — free)
and once to the tail (``zeros.at[dst].add(g)`` — a true scatter over random
indices). XLA lowers that scatter element-serialized; its share of the
epoch is not measured on the chip (no UMAP cell: ROADMAP.md Reach 9).

The edge list is STATIC per fit, so the randomness can be paid ONCE on the
host instead of every epoch on the device: sort the E = n*k edges by tail
index at graph-build time (:func:`build_tail_plan`), and each epoch becomes

    per-edge gradients --[one row gather by the static perm]--> tail-sorted
    --[this kernel]--> dense per-tile accumulation in VMEM.

The kernel walks output tiles of ``rows_per_tile`` embedding rows; because
edges arrive tail-sorted, each tile's contributions live in a CONTIGUOUS
slice of the edge stream, covered by a per-tile run of ``edges_per_block``
blocks (host-computed base/length, scalar-prefetched so the index maps are
static). Each block contributes via a one-hot contraction

    out(sub, R) += v(sub, EB) . onehot(R, EB)    # contract over EB

so the accumulator is written once per tile — no per-element scatter ever
reaches HBM. Out-of-tile edges in boundary blocks (and the sentinel-padded
tail of the stream) fall outside the tile's one-hot range and contribute
exactly zero — masking is free.

Determinism: the accumulation order WITHIN a tile is the sorted-edge order,
which differs from XLA's scatter order — results agree with the XLA path to
float tolerance, not bitwise (PARITY.md, ``TPUML_UMAP_SCATTER``). Segmented
and monolithic fits share one plan, so checkpoint bit-identity holds.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class TailCfg(NamedTuple):
    """Static (hashable) geometry of a tail plan — a jit static argument."""

    n: int                # true embedding rows
    dim: int              # embedding width (<= sub)
    sub: int              # sublane-padded width (multiple of 8)
    e: int                # true edge count n * k
    e_pad: int            # edge stream padded to edges_per_block multiples
    n_pad: int            # rows padded to rows_per_tile multiples
    rows_per_tile: int    # output tile width R (multiple of 128)
    edges_per_block: int  # edge block length EB (multiple of 128)
    max_nblocks: int      # widest per-tile block run (the static grid dim)


class TailPlan(NamedTuple):
    """Device-side arrays of the per-fit edge sort (a traced pytree)."""

    perm: jax.Array       # (e,) int32 edge permutation: tail-sorted order
    tails: jax.Array      # (1, e_pad) int32 sorted tails, sentinel-padded
    base: jax.Array       # (n_tiles,) int32 first edge BLOCK of each tile
    nblk: jax.Array       # (n_tiles,) int32 block-run length of each tile


def build_tail_plan(
    indices: np.ndarray,
    n: int,
    dim: int,
    rows_per_tile: int = 256,
    edges_per_block: int = 1024,
) -> Tuple[TailPlan, TailCfg]:
    """Host-side edge sort + tile coverage for one fitted graph.

    ``indices``: the (n, k) kNN tail ids (host copy — the graph is static
    per fit, so this runs once, outside every epoch). The returned plan is
    valid for any per-edge value stream laid out head-major (n * k rows),
    which is exactly ``g_att.reshape(-1, dim)``'s order.
    """
    tails = np.asarray(indices, dtype=np.int32).reshape(-1)
    e = tails.shape[0]
    perm = np.argsort(tails, kind="stable").astype(np.int32)
    tails_sorted = tails[perm]

    e_pad = e + (-e) % edges_per_block
    n_pad = n + (-n) % rows_per_tile
    n_tiles = n_pad // rows_per_tile
    total_blocks = e_pad // edges_per_block
    # Sentinel tails land past every tile's one-hot range: padded edges
    # contribute zero without any mask traffic.
    tails_full = np.full((e_pad,), n_pad, dtype=np.int32)
    tails_full[:e] = tails_sorted

    bounds = np.arange(n_tiles + 1, dtype=np.int64) * rows_per_tile
    cut = np.searchsorted(tails_sorted, bounds, side="left")
    start, stop = cut[:-1], cut[1:]
    base = np.minimum(start // edges_per_block, total_blocks - 1)
    last = np.ceil(stop / edges_per_block).astype(np.int64)
    nblk = np.maximum(last - base, 0)
    nblk[stop <= start] = 0
    # The widest run is the grid's static extent: rounded up to a power of
    # two so that the layout program is one a shape and not one a graph
    # (each new graph recompiled it: 63 s a fit at 250,000 rows on the
    # chip, PERF.md section 6); steps past a tile's run are skipped.
    widest = max(int(nblk.max()), 1) if n_tiles else 1
    max_nblocks = 1 << (widest - 1).bit_length()

    cfg = TailCfg(
        n=n, dim=dim, sub=dim + (-dim) % 8, e=e, e_pad=e_pad, n_pad=n_pad,
        rows_per_tile=rows_per_tile, edges_per_block=edges_per_block,
        max_nblocks=max_nblocks,
    )
    plan = TailPlan(
        perm=jnp.asarray(perm),
        tails=jnp.asarray(tails_full[None, :]),
        base=jnp.asarray(base.astype(np.int32)),
        nblk=jnp.asarray(nblk.astype(np.int32)),
    )
    return plan, cfg


def plan_feasible(n: int, k: int, dim: int) -> bool:
    """True when the bucketed kernel is worth dispatching: the one-hot
    block scratch plus in/out tiles sit well inside VMEM at the default
    geometry, and the embedding width fits one sublane tile."""
    if dim > 128:
        return False  # (sub, EB) v-tiles would crowd VMEM; XLA path instead
    # one-hot (R, EB) + v (sub, EB) + out (sub, R) + tails, f32/int32.
    sub = dim + (-dim) % 8
    elems = 256 * 1024 + sub * 1024 + sub * 256 + 1024
    return elems * 4 < (4 << 20) and n * k > 0


def _tail_kernel(base_ref, nblk_ref, t_ref, v_ref, out_ref, *, rows_per_tile):
    r = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(j < nblk_ref[r])
    def _():
        # onehot[c, e] = 1 iff edge e's tail is row r*R + c. Built in the
        # (R, EB) orientation so no (1, EB) -> (EB, 1) relayout is needed:
        # the row iota runs along sublanes, the tails broadcast along them.
        local = t_ref[:] - r * rows_per_tile  # (1, EB)
        oh = (
            jax.lax.broadcasted_iota(
                jnp.int32, (rows_per_tile, local.shape[1]), 0
            )
            == local
        ).astype(jnp.float32)  # (R, EB)
        # Three exact bf16 pieces of v (8 + 8 + 8 mantissa bits) against
        # the one-hot, which is exact in bf16: every product is exact and
        # the sums accumulate in f32 — the XLA scatter's numerics. A bare
        # f32 dot takes ONE bf16 pass on the chip (the interpreter's is
        # exact, so only a chip run shows it): 2.5e-4 of the largest row
        # sum against the scatter at 50k x 15 x 2, measured by
        # chip_smoke.py. HIGHEST would also split the one-hot, for six
        # passes where three are exact.
        v = v_ref[:]
        v_hi = v.astype(jnp.bfloat16).astype(jnp.float32)
        rest = v - v_hi
        v_mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
        for part in (v_hi, v_mid, rest - v_mid):
            out_ref[:] += jax.lax.dot_general(
                part, oh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT,
            )  # (sub, R)


@partial(jax.jit, static_argnames=("cfg", "interpret"))
def tail_accumulate(
    g: jax.Array, plan: TailPlan, cfg: TailCfg, interpret: bool = False
) -> jax.Array:
    """Sum per-edge rows into per-tail rows: the scatter-add replacement.

    ``g``: (e, dim) per-edge contributions in head-major edge order (the
    natural ``reshape(-1, dim)`` of the epoch's (n, k, dim) gradients).
    Returns (n, dim) with row t = sum of g over edges whose tail is t —
    same contraction the XLA scatter computes, dense-accumulated per tile.
    """
    if g.shape != (cfg.e, cfg.dim):
        raise ValueError(f"edge values {g.shape} != plan ({cfg.e}, {cfg.dim})")
    v = jnp.take(g, plan.perm, axis=0)  # (e, dim) tail-sorted, one row gather
    vt = jnp.pad(v.T, ((0, cfg.sub - cfg.dim), (0, cfg.e_pad - cfg.e)))
    n_tiles = cfg.n_pad // cfg.rows_per_tile

    def edge_block(r, j, base, nblk):
        # Past-the-run steps re-point at the run's last block: Mosaic sees
        # an unchanged index and skips the copy; @pl.when skips the math.
        return (0, base[r] + jnp.minimum(j, jnp.maximum(nblk[r] - 1, 0)))

    out = pl.pallas_call(
        partial(_tail_kernel, rows_per_tile=cfg.rows_per_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles, cfg.max_nblocks),
            in_specs=[
                pl.BlockSpec((1, cfg.edges_per_block), edge_block),
                pl.BlockSpec((cfg.sub, cfg.edges_per_block), edge_block),
            ],
            out_specs=pl.BlockSpec(
                (cfg.sub, cfg.rows_per_tile), lambda r, j, base, nblk: (0, r)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((cfg.sub, cfg.n_pad), jnp.float32),
        interpret=interpret,
    )(plan.base, plan.nblk, plan.tails, vt)
    return out[: cfg.dim, : cfg.n].T


__all__ = [
    "TailCfg",
    "TailPlan",
    "build_tail_plan",
    "plan_feasible",
    "tail_accumulate",
]
