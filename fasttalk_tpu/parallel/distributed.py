"""Multi-host initialisation: JAX distributed runtime over DCN.

The reference's multi-accelerator story ended at one host: NCCL lived
inside the vLLM container and scaled only across the GPUs of a single
machine (reference: docker-compose.vllm.yml:42 --tensor-parallel-size).
The TPU-native equivalent of its "communication backend" is two-layer:
XLA collectives over ICI within a slice (emitted by GSPMD from the
sharding rules in parallel/sharding.py), and the JAX distributed runtime
over DCN across hosts — which this module initialises.

On a multi-host TPU slice (GKE / queued resources) — recognised by
``TPU_WORKER_HOSTNAMES`` listing more than one worker, or a multislice
coordinator — ``initialize()`` with no env overrides lets JAX
auto-discover the coordinator from the TPU metadata. Elsewhere (CPU
fleets, explicit setups), the standard ``TPU_COORDINATOR_ADDR`` /
``TPU_NUM_PROCESSES`` / ``TPU_PROCESS_ID`` env vars drive it. A single
host never goes through discovery: TPU VM images set
``TPU_WORKER_HOSTNAMES=localhost`` on one-host machines too, and
discovery on a machine with no metadata server or network is a hang or
a long timeout at start. After initialisation, ``jax.devices()`` spans
every host and the meshes built by parallel/mesh.py place DP/SP axes
across DCN and TP within ICI (mesh axis order is chosen so the
innermost axis — "tp" — maps to the fastest links).
"""

from __future__ import annotations

import os

from fasttalk_tpu.utils.logger import get_logger

log = get_logger("parallel.distributed")

_initialized = False


def maybe_initialize() -> bool:
    """Initialise the JAX distributed runtime when configured.

    Returns True when running (or already running) multi-process.
    No-ops (without importing jax) when neither env configuration nor a
    multi-host TPU environment is present, so single-host serving never
    pays the coordinator setup. A configured or detected multi-host
    environment that fails to initialise raises: continuing single-host
    would serve a fraction of the slice and look healthy.
    """
    global _initialized
    if _initialized:
        return True
    coordinator = os.environ.get("TPU_COORDINATOR_ADDR", "")
    nprocs = os.environ.get("TPU_NUM_PROCESSES", "")
    pid = os.environ.get("TPU_PROCESS_ID", "")
    workers = [h for h in os.environ.get(
        "TPU_WORKER_HOSTNAMES", "").split(",") if h.strip()]
    if coordinator and nprocs:
        kwargs = dict(coordinator_address=coordinator,
                      num_processes=int(nprocs),
                      process_id=int(pid or 0))
    elif len(workers) > 1 or \
            os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        kwargs = {}  # pod / multislice: JAX discovers the coordinator
    else:
        return False
    import jax

    jax.distributed.initialize(**kwargs)
    _initialized = True
    log.info("distributed runtime up",
             process_index=jax.process_index(),
             process_count=jax.process_count(),
             global_devices=len(jax.devices()),
             local_devices=len(jax.local_devices()))
    return True


def process_info() -> dict:
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_device_count": len(jax.devices()),
        "local_device_count": len(jax.local_devices()),
        "initialized": _initialized,
    }
