"""``repro.autotune`` — knob search + persistent tuning cache (ISSUE 8).

Three layers:

* ``cache`` — the JSON tuning cache (``results/autotune_cache.json`` by
  default, gitignored; ``REPRO_AUTOTUNE_CACHE`` overrides) with schema and
  knob-revision pins, a value allowlist, and counted fallbacks for every
  invalid-file class.
* ``measure`` — candidate scoring: median wall time on real devices,
  deterministic collective count/byte cost model on CPU hosts.
* ``search`` — coordinate-descent sweep of (chunk, field_dtype,
  plan_dtype, interp_method) over the compiled Hessian matvec, plus
  preconditioner races and mesh-layout records.

Consumers consult through two entry points here: ``consult_gn`` (called by
``gn.solve``/``make_cohort_step``/``register`` when ``GNConfig.autotune !=
"off"``) and ``consult_ctx`` (called by ``DistContext.__init__``).  Both
only fill knobs still at their default sentinels — an explicit value
always wins — and a missing/invalid cache is a silent no-op, so tuning can
never change behavior the user pinned by hand.
"""
from __future__ import annotations

from repro.autotune.cache import (
    KNOBS_REV,
    SCHEMA_VERSION,
    TunedConfig,
    TuningCache,
    cell_key,
    default_cache_path,
    resolve_tuned,
    tuned_replace,
)

__all__ = [
    "SCHEMA_VERSION",
    "KNOBS_REV",
    "TunedConfig",
    "TuningCache",
    "cell_key",
    "default_cache_path",
    "resolve_tuned",
    "tuned_replace",
    "consult_gn",
    "consult_ctx",
    "sweep_cell",
    "sweep_mesh_layouts",
]

# default sentinels of the GNConfig perf knobs the resolver may fill
_GN_DEFAULTS = {"interp_method": "auto", "plan_dtype": None, "field_dtype": None}


def _ndev_of(ops) -> int:
    mesh = getattr(getattr(ops, "fft", None), "mesh", None)
    return int(mesh.devices.size) if mesh is not None else 1


def consult_gn(cfg, grid, ops):
    """Fill still-at-default perf knobs of a ``GNConfig`` from the cache.

    ``autotune="sweep"`` additionally runs ``search.sweep_cell`` on a cache
    miss when ``ops`` is backed by a device mesh (a local solve has no
    collectives to tune — the sweep is skipped and defaults stand)."""
    tuned = resolve_tuned(grid.shape, _ndev_of(ops), beta=cfg.beta)
    if tuned is None and cfg.autotune == "sweep":
        mesh = getattr(getattr(ops, "fft", None), "mesh", None)
        if mesh is not None:
            from repro.autotune.search import sweep_cell

            fft = ops.fft
            sweep_cell(grid, mesh, beta=cfg.beta, axes=fft.axes)
            tuned = resolve_tuned(grid.shape, _ndev_of(ops), beta=cfg.beta)
    if tuned is None:
        return cfg
    return tuned_replace(cfg, tuned, _GN_DEFAULTS)


def consult_ctx(ctx) -> dict:
    """Tuned knobs for a ``DistContext`` under construction.

    Returns only the knobs the context should adopt: those still at their
    constructor sentinels (``chunk=None``, ``interp_method="auto"``,
    ``plan_dtype=None``, ``field_dtype=None``).  Beta is not known at
    context-build time, so the lookup uses the exact-cell beta-agnostic
    entry (``beta-any``)."""
    tuned = resolve_tuned(ctx.grid.shape, int(ctx.mesh.devices.size), beta=None)
    if tuned is None:
        return {}
    out: dict = {}
    if ctx.chunk is None and tuned.chunk is not None:
        out["chunk"] = tuned.chunk
    if ctx.interp_method == "auto" and tuned.interp_method is not None:
        out["interp_method"] = tuned.interp_method
    if ctx.plan_dtype is None and tuned.plan_dtype is not None:
        out["plan_dtype"] = tuned.plan_dtype
    if ctx.field_dtype is None and tuned.field_dtype is not None:
        out["field_dtype"] = tuned.field_dtype
    return out


def sweep_cell(*args, **kwargs):
    """Lazy re-export of ``repro.autotune.search.sweep_cell``."""
    from repro.autotune import search

    return search.sweep_cell(*args, **kwargs)


def sweep_mesh_layouts(*args, **kwargs):
    """Lazy re-export of ``repro.autotune.search.sweep_mesh_layouts``."""
    from repro.autotune import search

    return search.sweep_mesh_layouts(*args, **kwargs)
