"""Multi-host (pod / pod-slice) initialization and mesh construction.

Reference: Spark's driver/executor RPC + Rabit tracker launch is the reference's
multi-machine substrate (SURVEY §5.8).  TPU-native equivalent: ``jax.distributed``
for process-group bootstrap, one process per host, with XLA collectives riding
ICI inside a slice and DCN across slices.  The framework's stages stay unchanged
— the same ``use_mesh`` context works on a multi-host mesh because every
collective is inserted by XLA from sharding annotations, never hand-written.

Usage (one process per host, e.g. under a pod launcher):

    from transmogrifai_tpu.parallel import distributed
    distributed.initialize()                # env-driven (TPU pods auto-detect)
    mesh = distributed.global_mesh(n_model=2)
    with use_mesh(mesh):
        model = workflow.train()
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np

from .mesh import DATA_AXIS, MODEL_AXIS, make_mesh


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Bootstrap the jax.distributed process group (idempotent).

    On TPU pods all three arguments auto-detect from the environment; on
    CPU/GPU fleets pass them explicitly or via JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID.  Single-process runs are a no-op.
    """
    if is_initialized():
        return
    kwargs = {}
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if coordinator_address:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if process_id is not None:
        kwargs["process_id"] = process_id
    if not kwargs and not _pod_environment():
        # single host, nothing to bootstrap.  Decided WITHOUT touching
        # jax.process_count(): that would initialize the local backend and
        # jax.distributed.initialize must run before any JAX computation.
        return
    try:
        jax.distributed.initialize(**kwargs)
    except (ValueError, RuntimeError) as e:
        if kwargs:
            raise  # explicit config must fail loudly
        n_implied = _implied_worker_count()
        if n_implied > 1:
            # markers say this process is one of N>1 workers: continuing
            # single-host would run N duplicate full fits racing on the same
            # model/metrics outputs — fail instead (ADVICE r1)
            raise RuntimeError(
                f"jax.distributed auto-bootstrap failed ({e}) but environment "
                f"markers imply {n_implied} workers; refusing to continue as a "
                "single-host run. Pass coordinator_address/num_processes/"
                "process_id explicitly.") from e
        # pod-like markers but genuinely single-worker (e.g. a 1-host slice
        # whose launcher still exports them): proceed single-host, but say so.
        import logging

        logging.getLogger(__name__).warning(
            "jax.distributed auto-bootstrap failed (%s); continuing as a "
            "single-host run. If this IS a multi-host pod, pass "
            "coordinator_address/num_processes/process_id explicitly.", e)


def _implied_worker_count() -> int:
    """Worker count the launcher markers imply; 1 when ambiguous/absent.

    Covers every marker ``_pod_environment`` recognizes: explicit counts
    (hostname lists, SLURM/OMPI sizes), worker indices (a task id of k implies
    at least k+1 workers), and multislice coordination (megascale jobs span
    multiple slices by construction).
    """
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    counts = [len([h for h in hosts.split(",") if h.strip()])]
    for var in ("SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE"):
        try:
            counts.append(int(os.environ.get(var, "1")))
        except ValueError:
            pass
    for var in ("TPU_WORKER_ID", "CLOUD_TPU_TASK_ID"):
        try:
            counts.append(int(os.environ.get(var, "-1")) + 1)
        except ValueError:
            pass
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        counts.append(2)
    return max(counts + [1])


def _pod_environment() -> bool:
    """Multi-host launcher markers that jax.distributed auto-detects from."""
    return any(v in os.environ for v in (
        "TPU_WORKER_HOSTNAMES", "CLOUD_TPU_TASK_ID", "MEGASCALE_COORDINATOR_ADDRESS",
        "TPU_WORKER_ID", "SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE"))


def is_initialized() -> bool:
    return jax.distributed.is_initialized()


def process_info() -> dict:
    """Topology summary for logs/metrics (OpSparkListener's appInfo role)."""
    return {
        "processId": jax.process_index(),
        "processCount": jax.process_count(),
        "localDevices": len(jax.local_devices()),
        "globalDevices": jax.device_count(),
        "platform": jax.default_backend(),
    }


def global_mesh(n_model: int = 1, devices: Optional[Sequence] = None,
                strict_topology: bool = True):
    """A (data, model) dp x mp mesh over ALL processes' devices.

    Device order follows ``jax.devices()`` (hosts-major), so the data axis
    splits contiguously across hosts: row shards ride ICI within a host's
    slice and DCN only at host boundaries — the layout the scaling playbook
    prescribes for data parallelism.  The model axis is the FAST axis of the
    (data, model) factoring, so with ``n_model <= local devices`` every
    model-axis group lives inside one host's slice: the fold x grid batch
    reshards over ICI only, never DCN (the SNIPPETS [1] rule — model
    parallel between nodes is bad).  ``strict_topology=False`` downgrades a
    host-crossing model axis to a warning (expert escape hatch).
    """
    devs = np.asarray(devices if devices is not None else jax.devices())  # opcheck: allow(TM301) Device objects, not a traced jax value
    n_model = int(n_model)
    if n_model < 1 or devs.size % n_model != 0:
        raise ValueError(
            f"n_model={n_model} must divide the {devs.size} global devices")
    # model is the FAST axis of make_mesh's (n_data, n_model) factoring, so
    # each consecutive n_model-sized run of ``devs`` is one model group.
    # Default path: the hosts-major jax.devices() contract makes per-host
    # divisibility the check.  Explicit ``devices``: a per-host count is
    # meaningless (the list may be any subset/order), so check each group's
    # owning processes directly off the Device objects.
    if devices is None:
        n_local = len(jax.local_devices())
        crossing = jax.process_count() > 1 and n_local % n_model != 0
    else:
        flat = devs.reshape(-1)
        crossing = n_model > 1 and any(
            len({getattr(d, "process_index", 0)
                 for d in flat[i:i + n_model]}) > 1
            for i in range(0, flat.size, n_model))
    if crossing:
        msg = (f"a model-parallel group of {n_model} devices would span "
               f"hosts and its reshards would ride DCN instead of ICI; "
               f"pick n_model so each group of {n_model} consecutive "
               f"devices lives on one process")
        if strict_topology:
            raise ValueError(msg)
        import logging

        logging.getLogger(__name__).warning("%s (continuing: "
                                            "strict_topology=False)", msg)
    return make_mesh(n_data=devs.size // n_model, n_model=n_model, devices=devs)


def host_local_rows(n_global_rows: int) -> slice:
    """This process's contiguous row range for host-sharded ingest: each host
    reads only its slice of the input (the readers' multi-host contract)."""
    return host_row_span(n_global_rows, jax.process_index(),
                         jax.process_count())


def host_row_span(n_global_rows: int, process_id: int,
                  process_count: int) -> slice:
    """The contiguous row range process ``process_id`` of ``process_count``
    owns — the pure arithmetic under :func:`host_local_rows`, factored out so
    single-process tests can exercise every host's span (the mocked
    ``process_index``/``process_count`` pattern in tests/test_distributed.py)
    and so chunked readers can enumerate peer spans without touching jax."""
    per = -(-int(n_global_rows) // int(process_count))
    start = min(int(process_id) * per, int(n_global_rows))
    return slice(start, min(start + per, int(n_global_rows)))


def host_row_spans(n_global_rows: int,
                   process_count: Optional[int] = None) -> list:
    """Every process's row span, in process order.  The spans partition
    ``range(n_global_rows)`` exactly — the decomposition contract the
    global-array assembly below (and the two-simulated-host composition test)
    rests on."""
    pc = jax.process_count() if process_count is None else int(process_count)
    return [host_row_span(n_global_rows, pid, pc) for pid in range(pc)]


def global_row_array(local_rows, n_global_rows: Optional[int] = None,
                     mesh=None):
    """A GLOBAL row-sharded jax.Array assembled from THIS process's row
    block.

    ``local_rows`` holds exactly this process's ``host_local_rows(n_global)``
    slice (each host decodes only its own span — the chunked-ingestion
    multi-host contract); the returned array is the (n_global, ...) logical
    array sharded over the mesh's data axis, built with
    ``jax.make_array_from_process_local_data`` so no host ever materializes
    another host's rows.  Single-process (or no mesh): an ordinary
    :func:`~.mesh.place_rows` placement — the two paths produce the same
    logical array, which is what lets every test above this seam run
    single-process.

    The caller pads ``n_global_rows`` to the data-axis multiple BEFORE
    slicing spans (``pad_rows_bucketed_for_mesh`` — zero rows with zero
    weights), so spans stay even across hosts.
    """
    from .mesh import current_mesh, place_rows, row_sharding

    mesh = mesh if mesh is not None else current_mesh()
    local = np.asarray(local_rows)
    n_global = int(n_global_rows) if n_global_rows is not None \
        else local.shape[0]
    if mesh is None or jax.process_count() == 1:
        if local.shape[0] != n_global:
            raise ValueError(
                f"single-process assembly expects the full {n_global} rows, "
                f"got {local.shape[0]} (host_local_rows of one process is "
                f"the whole table)")
        return place_rows(local, mesh)
    span = host_local_rows(n_global)
    if local.shape[0] != span.stop - span.start:
        raise ValueError(
            f"process {jax.process_index()} owns rows [{span.start}, "
            f"{span.stop}) of {n_global} but got a {local.shape[0]}-row "
            f"block; decode exactly host_local_rows(n_global)")
    global_shape = (n_global,) + tuple(local.shape[1:])
    return jax.make_array_from_process_local_data(
        row_sharding(mesh), local, global_shape)
