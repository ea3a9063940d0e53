"""Entry ``selector_fit_mesh``: ``selector_fit``'s timed call — one whole
``ModelSelector.fit(dataset)`` — under ``use_mesh(make_mesh(*config["mesh"]))``,
the way a user shards a training job's table by rows over the chips of one
host.  Everything but the mesh is ``selector_fit``'s own code, imported: the
selector built from the configuration, the record of a fit, the sampled
scores, the release, and the comparison with the plain reference.

On top of ``selector_fit``'s reasons a fit counts as failed whole when the
program's degradation counter moved (``placement_stats()["mesh"]``: an array
asked to be sharded was replicated because a size does not divide its axis),
or when the table's placed array does not hold an equal share of the padded
rows on each device of the data axis.  A program without the counter (one
older than it) is held to the shares alone.

The comparison holds every timed fit to the same reference at the timed
size.  The table does not fit one chip, so it is handed to the reference
sharded by rows over the same chips, placed with plain ``jax.sharding``
(nothing of the program); the compiler partitions the reference's float32
programs from there.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from . import selector_fit as single
from .selector_fit import (  # noqa: F401 — the harness calls these as ours
    collect, enable_cache, release)


def _mesh(config: Dict[str, Any]):
    import jax

    from transmogrifai_tpu.parallel.mesh import make_mesh

    n_data, n_model = (int(v) for v in config["mesh"])
    return make_mesh(n_data, n_model,
                     devices=jax.devices()[:n_data * n_model])


def _mesh_counters() -> Dict[str, float]:
    """The program's ``mesh`` placement counters; empty on a program that
    has none."""
    from transmogrifai_tpu.parallel.mesh import placement_stats

    return {f"mesh_{k}": v
            for k, v in (placement_stats().get("mesh") or {}).items()}


def shard_faults(placed, n_data: int) -> List[str]:
    """Why ``placed`` is not an equal share of its rows on each of ``n_data``
    devices (empty when it is)."""
    shards = placed.addressable_shards
    rows = [int(s.data.shape[0]) for s in shards]
    n, share = int(placed.shape[0]), int(placed.shape[0]) // n_data
    # a model axis of m holds each share m times: still one share a device
    if (rows == [share] * len(shards) and share * n_data == n
            and len({s.device for s in shards}) == len(shards)
            and len(shards) % n_data == 0):
        return []
    return [f"the table's {n} padded rows lie as {sorted(rows)} on "
            f"{len(shards)} devices, not {share} on each"]


def _table_faults(state) -> List[str]:
    """The table as the program placed it for the fit just made (a cache hit:
    the fit's own array, nothing is placed here)."""
    from transmogrifai_tpu.parallel.mesh import (
        place_rows_bucketed_cached, use_mesh)

    x = state.dataset["features"].data
    with use_mesh(state.mesh):
        placed, _ = place_rows_bucketed_cached(np.asarray(x, np.float32))
    return shard_faults(placed, int(state.config["mesh"][0]))


def setup(config: Dict[str, Any], table):
    from transmogrifai_tpu.parallel.mesh import use_mesh

    mesh = _mesh(config)
    with use_mesh(mesh):        # the warm-up fit compiles the mesh's programs
        state = single.setup({**config, "mesh": None}, table)
    state.config, state.mesh = config, mesh
    faults = _table_faults(state)
    if faults:
        raise RuntimeError(f"the warm-up fit failed: {faults}")
    return state


def step(state, may_compile: bool = False) -> Dict[str, Any]:
    """One whole ``fit`` under the mesh; ``selector_fit``'s record with the
    mesh counters' movement beside its own and the recorder's ``mesh``."""
    from transmogrifai_tpu.parallel.mesh import use_mesh

    before = _mesh_counters()
    with use_mesh(state.mesh):
        rec = single.step(state, may_compile)
    moved = {k: v - before[k] for k, v in _mesh_counters().items()}
    rec["counters"].update(moved)
    rec["mesh"] = getattr(state.selector.last_fit_profile, "mesh", None)
    why = _table_faults(state)
    if moved.get("mesh_degraded"):
        why.append(f"mesh_degraded moved by {moved['mesh_degraded']} "
                   f"({moved['mesh_bytes_degraded']} bytes replicated where "
                   "a shard was asked for)")
    if why:
        rec["why_failed"] = rec["why_failed"] + why
        rec["failed"] = rec["attempted"]    # the whole fit counts as failed
    return rec


def shard_table(table, chips: int):
    """``table`` with ``x`` on the first ``chips`` devices, sharded by rows
    with plain ``jax.sharding``; the host table where the rows do not divide
    (a tiny guard size: one device holds it)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    if chips <= 1 or table.x.shape[0] % chips:
        return table
    rows = NamedSharding(Mesh(np.array(jax.devices()[:chips]), ("rows",)),
                         PartitionSpec("rows"))
    return type(table)(jax.device_put(table.x, rows), table.y)


def compare(config: Dict[str, Any], table, records: List[Dict[str, Any]],
            seed: int, precision: str = "float32", control: bool = False):
    """``selector_fit.compare`` — the same four numbers, the same reference
    files — with the table sharded by rows over the mesh's chips: its
    ``jnp.asarray`` takes a placed array as it is, the labels and fold
    weights follow as host arrays, and the compiler partitions every
    reference program along the rows from the table's sharding."""
    chips = math.prod(int(size) for size in config["mesh"])
    return single.compare(config, shard_table(table, chips), records, seed,
                          precision=precision, control=control)
