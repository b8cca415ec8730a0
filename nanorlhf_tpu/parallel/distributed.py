"""Multi-host initialization — the TPU-native distributed backend.

The reference's distributed story is accelerate → torch.distributed → NCCL,
exercised at world_size 1 (SURVEY.md §5.8; `/root/reference/GRPO/
grpo_trainer.py:218,242`). Its used collective surface — one broadcast of a
run timestamp, metric gathers, and gradient sync — all become XLA
collectives inside the compiled step here. What remains host-side is
process-group bring-up, which this module wraps:

- on a TPU pod slice, `jax.distributed.initialize()` discovers coordinator
  and process ids from the TPU environment automatically;
- across slices (DCN), the standard env vars / explicit args apply;
- mesh axes should map (data → DCN × ICI, fsdp/tensor → ICI only) so
  parameter collectives never cross the slow DCN links.
"""

from __future__ import annotations

import os

import jax


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> dict:
    """Bring up jax.distributed for multi-host runs; no-op for single host.

    Returns a summary dict (process_index, process_count, device counts).
    Safe to call when already initialized or on a single host.
    """
    should_init = (
        coordinator_address is not None
        or os.environ.get("COORDINATOR_ADDRESS")
        or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
        or os.environ.get("TPU_WORKER_HOSTNAMES", "").count(",") > 0
    )
    if should_init and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
    }


def broadcast_host_value(value: int) -> int:
    """Agree on process 0's value across all hosts (run-timestamp parity with
    `broadcast(time_tensor, 0)`, `grpo_trainer.py:241-242`)."""
    if jax.process_count() == 1:
        return int(value)
    from jax.experimental import multihost_utils

    import numpy as np

    return int(multihost_utils.broadcast_one_to_all(np.int32(value)))
