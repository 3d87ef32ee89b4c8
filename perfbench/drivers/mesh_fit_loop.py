"""mesh_fit_loop — ``fit_loop``'s window on rows sharded over the cell's chips.

The cell's configuration is an existing one (``pca_3000``); what changes on
a mesh is where the rows live and which estimator takes them. So this driver
hands ``fit_loop.run`` a copy of the configuration in which

* the generator is :func:`rows_on_mesh`: the configuration's own generator,
  jitted whole as before, its rows laid out row-sharded over a
  ``make_mesh((chips, 1))`` of all the chips (``500,000`` rows and 6.0 GB a
  chip in ``pca_3000.mesh4``), no row on the host and none on one chip;
* the estimator class is :func:`estimator`: the configuration's
  class built with ``mesh=`` that mesh.

Everything else, the warm-up, the window, the trace, the fits' host reads and
the plain reference with the configuration's limits, is ``fit_loop``'s and
the configuration's, unchanged. ``perfbench.control`` makes its rows through
``fit_loop`` on one chip and does not run this driver.
"""

from __future__ import annotations

from perfbench import data
from perfbench.drivers import fit_loop


def _mesh():
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    return make_mesh()  # every chip on the data axis


def rows_on_mesh(key, n: int, d: int, *, generator: str, **params):
    """``generator``'s rows, row-sharded over the mesh: the layout is a
    constraint on the generator's own output inside ``data.generate``'s one
    jitted call, so each chip writes its own rows."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    x = data.resolve(generator)(key, n, d, **params)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_mesh(), P(DATA_AXIS, None)))


_on_one_chip = []  # the configuration's own estimator class, set by on_mesh


def estimator():
    """``fit_loop.build_estimator``'s class in this driver's configuration:
    the configuration's own estimator, over the mesh."""
    return data.resolve(_on_one_chip[0])(mesh=_mesh())


def on_mesh(config: dict) -> dict:
    """The configuration as this driver runs it (see the module's text)."""
    gen, est = config["data"], config["estimator"]
    _on_one_chip[:] = [est["class"]]
    return {
        **config,
        "data": {
            **gen,
            "generator": "perfbench.drivers.mesh_fit_loop:rows_on_mesh",
            "params": {**gen["params"], "generator": gen["generator"]},
        },
        "estimator": {
            **est,
            "class": "perfbench.drivers.mesh_fit_loop:estimator",
            "on_one_chip": est["class"],
        },
    }


def one_chip_rows(ctx, x) -> dict:
    """A planted fault of this cell: the model fitted on ONE chip's share of
    the rows (its shard), as a fit that lost the merge across chips would
    hand back. It has to come out not correct."""
    spec = ctx.config["estimator"]
    model = data.resolve(spec.get("on_one_chip", spec["class"]))()
    for name, value in spec["set"].items():
        getattr(model, "set" + name)(value)
    return fit_loop.read_model(model.fit(x.addressable_shards[0].data), ctx.config)


def run(ctx) -> None:
    ctx.config = on_mesh(ctx.config)
    fit_loop.run(ctx)
