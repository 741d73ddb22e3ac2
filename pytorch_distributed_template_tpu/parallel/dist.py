"""Distributed runtime introspection and host-level collectives.

TPU-native analogue of /root/reference/utils/dist.py — the single seam where
"distributed" touches every layer of the reference (imported by its config
parser, trainer, data loader, and entry points). Key translation:

- NCCL process group init (`train.py:23-29`)   -> ``initialize()`` calling
  ``jax.distributed.initialize`` for multi-host (DCN rendezvous), a graceful
  no-op single-host — preserving the reference's degradation contract
  (utils/dist.py:8-14) so the whole stack runs without a launcher.
- ``get_rank``/``get_world_size``              -> ``process_index``/
  ``process_count`` (host granularity; device parallelism lives in the mesh,
  not here).
- ``synchronize()`` = guarded barrier          -> ``sync_global_devices`` at
  checkpoint/epoch edges only; inside ``jit`` XLA's SPMD needs no barrier.
- pickle-over-NCCL ``all_gather`` of arbitrary objects (utils/dist.py:34-74)
  -> ``all_gather_object`` over DCN host collectives; same pickle/pad/unpad
  dance but never touching accelerator interconnect — device-side data should
  be reduced in-graph with ``psum`` instead.
"""
from __future__ import annotations

import os
import pickle
from typing import Any, List, Optional

import jax
import numpy as np

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Initialize multi-host JAX when requested; no-op otherwise.

    Multi-host is entered when explicit args are given or the standard env
    vars (``JAX_COORDINATOR_ADDRESS``/cluster autodetect) are present. On a
    single host this is a no-op, mirroring the reference's behavior of only
    entering ``init_process_group`` when ``WORLD_SIZE > 1``
    (/root/reference/train.py:20-29).
    """
    global _initialized
    if _initialized:
        return
    explicit = coordinator_address is not None
    env_requested = "JAX_COORDINATOR_ADDRESS" in os.environ or (
        "COORDINATOR_ADDRESS" in os.environ and "NUM_PROCESSES" in os.environ
    )
    # Cloud TPU pod slices advertise their peer hosts; when more than one is
    # listed, argument-free jax.distributed.initialize() autodetects the
    # cluster (coordinator, process count, process id).
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    pod_autodetect = len([h for h in hostnames.split(",") if h.strip()]) > 1

    if explicit or env_requested:
        if num_processes is None:
            env_np = os.environ.get("NUM_PROCESSES")
            num_processes = int(env_np) if env_np else None
        if process_id is None:
            env_pid = os.environ.get("PROCESS_ID")
            process_id = int(env_pid) if env_pid is not None else None
        jax.distributed.initialize(
            coordinator_address=coordinator_address
            or os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS"),
            num_processes=num_processes,
            process_id=process_id,
        )
    elif pod_autodetect:
        jax.distributed.initialize()
    _initialized = True


def process_index() -> int:
    """This host's index (0-based). Reference: ``get_rank`` (utils/dist.py:17-22)."""
    return jax.process_index()


def process_count() -> int:
    """Number of participating hosts. Reference: ``get_world_size`` (utils/dist.py:24-29)."""
    return jax.process_count()


def is_main_process() -> bool:
    """Reference: ``is_main_process`` (utils/dist.py:31-32). Gates all I/O."""
    return jax.process_index() == 0


def local_device_count() -> int:
    return jax.local_device_count()


def global_device_count() -> int:
    return jax.device_count()


def device_summary() -> dict:
    """What the run actually ran on, as jax reports it — written into
    every ``summary.json`` so a result can never be read as a device
    number it is not."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def synchronize(name: str = "sync") -> None:
    """Barrier across hosts. Reference: ``synchronize`` (utils/dist.py:7-15).

    Needed only at host-side edges (checkpoint save, epoch consensus); SPMD
    programs under ``jit`` are already synchronized by their collectives.
    """
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def all_gather_object(obj: Any) -> List[Any]:
    """All-gather arbitrary picklable objects across hosts.

    The reference's comms workhorse (utils/dist.py:34-74) pickles, pads to the
    max size, and runs a NCCL byte-tensor all_gather on *GPU*. Here the same
    pickle/pad protocol runs over the host (DCN) collective —
    ``multihost_utils.process_allgather`` — keeping Python objects off the
    accelerator interconnect entirely. Degrades to ``[obj]`` single-host.

    Used for: early-stop consensus (reference base_trainer.py:101-107) and any
    host-side metadata exchange. Device metrics should never come through
    here — reduce them in-graph.
    """
    if jax.process_count() == 1:
        return [obj]
    from jax.experimental import multihost_utils

    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    local_size = np.array([payload.size], dtype=np.int64)
    sizes = multihost_utils.process_allgather(local_size)  # [P, 1]
    sizes = np.asarray(sizes).reshape(-1)
    max_size = int(sizes.max())
    padded = np.zeros(max_size, dtype=np.uint8)
    padded[: payload.size] = payload
    gathered = np.asarray(multihost_utils.process_allgather(padded))  # [P, max]
    return [
        pickle.loads(gathered[i, : int(sizes[i])].tobytes())
        for i in range(gathered.shape[0])
    ]


def broadcast_object(obj: Any) -> Any:
    """Broadcast a picklable object from host 0 to all hosts.

    Two fixed-shape ``broadcast_one_to_all`` rounds (size, then payload) so
    only host 0's bytes move over DCN — O(size), not the O(P x max_size) an
    all-gather would cost — and non-root objects need not be picklable.
    """
    if jax.process_count() == 1:
        return obj
    from jax.experimental import multihost_utils

    if jax.process_index() == 0:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    else:
        payload = np.zeros(0, dtype=np.uint8)
    size = int(
        multihost_utils.broadcast_one_to_all(np.array([payload.size], np.int64))[0]
    )
    buf = np.zeros(size, dtype=np.uint8)
    buf[: payload.size] = payload[:size] if payload.size else payload
    data = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    return pickle.loads(data.tobytes())
