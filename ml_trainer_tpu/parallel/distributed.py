"""Multi-host rendezvous — the ``dist.init_process_group`` analog.

The reference rendezvous is ``dist.init_process_group(backend='smddp')``
(ref: src/trainer.py:59), with backend strings naming collective libraries
(SMDDP/NCCL/gloo, ref: main.py:72-73).  The TPU-native equivalent is
``jax.distributed.initialize()``: each host joins a coordination service,
after which ``jax.devices()`` spans the whole slice/pod and a single mesh
covers ICI and DCN uniformly.  Backend strings are kept for config parity
but select behaviour, not a library: ``tpu`` expects real TPU hosts (env
auto-detection), ``cpu`` is the simulated-mesh path used by tests —
the analog of the reference's gloo/local_gpu staging story (SURVEY.md §4).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_INITIALIZED = False


def initialize_distributed(
    backend: str = "tpu",
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Idempotent multi-host init.  Single-process runs are a no-op, exactly
    as the reference skips ``init_process_group`` when ``is_parallel`` is
    False (ref: src/trainer.py:57-71)."""
    global _INITIALIZED
    if _INITIALIZED:
        return
    if backend == "cpu":
        # Simulated mesh on the host platform; no rendezvous needed.
        _INITIALIZED = True
        return
    explicit = coordinator_address is not None
    # Rendezvous only when the environment describes MORE THAN ONE host.
    # A single TPU host also carries TPU_WORKER_ID / CLOUD_TPU_TASK_ID,
    # and there jax.distributed.initialize() has nobody to meet: its
    # cluster auto-detection asks a metadata service a sealed machine
    # cannot reach.  One process drives all the chips of one host.
    hosts = [
        h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
        if h.strip()
    ]
    auto = "COORDINATOR_ADDRESS" in os.environ or len(hosts) > 1
    if explicit or auto:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    _INITIALIZED = True


def process_count() -> int:
    """World size analog (ref: src/trainer.py:60-63 ``dist.get_world_size``),
    counted in hosts — intra-host parallelism is the mesh's job."""
    return jax.process_count()


def process_index() -> int:
    """Rank analog (ref: src/trainer.py:61 ``dist.get_rank``)."""
    return jax.process_index()


def is_primary() -> bool:
    """Rank-0 check used for checkpoint/history writes
    (ref: src/trainer.py:252-254)."""
    return jax.process_index() == 0
