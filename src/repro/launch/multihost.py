"""Multi-host bootstrap for real TPU pods.

On-cluster entry point: every host calls ``init_distributed()`` before
any other jax usage; the coordinator address/process indices come from
the TPU metadata environment (GKE/TPU-VM set these) or explicit flags.
After init, ``jax.devices()`` spans the whole slice and the exact same
``make_production_mesh()`` / cell-builder code used by the CPU dry-run
drives real silicon — that equivalence is the point of the dry-run.

Fault tolerance at this layer (DESIGN.md §6):
  * restartable: training state lives in mesh-agnostic checkpoints; any
    replacement host set re-initialises and restores (elastic pod count);
  * deterministic data: every host regenerates its shard of any global
    batch from (seed, step) — no data-service handoff on failover;
  * straggler detection: a lightweight heartbeat barrier each
    ``--heartbeat-every`` steps; hosts that miss ``--max-missed``
    heartbeats trigger a controlled save-and-exit so the scheduler can
    reschedule the slice (preemption-safe).
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Initialise jax.distributed from flags or scheduler environment."""
    coordinator = coordinator or os.environ.get("COORDINATOR_ADDRESS")
    if coordinator is None:
        # single-host run (tests / CPU dry-run): nothing to do
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes
                          or os.environ.get("NUM_PROCESSES", 1)),
        process_id=int(process_id or os.environ.get("PROCESS_ID", 0)))


class Heartbeat:
    """Cross-host liveness barrier: a tiny psum each interval; a timeout
    means a peer is gone or wedged -> save and exit non-zero so the
    scheduler restarts the slice from the latest checkpoint."""

    def __init__(self, interval_steps: int = 100, timeout_s: float = 300.0):
        self.interval = interval_steps
        self.timeout = timeout_s
        self._last = time.time()

    def maybe_beat(self, step: int, on_failure=None) -> None:
        if step % self.interval:
            return
        try:
            # an all-reduce over one scalar doubles as the barrier
            jax.device_get(_psum_one())
            self._last = time.time()
        except Exception:
            if on_failure is not None:
                on_failure()
            raise


def _psum_one():
    import jax.numpy as jnp
    from repro.compat import shard_map
    from jax.sharding import PartitionSpec as P
    import numpy as np
    devs = np.array(jax.devices())
    mesh = jax.sharding.Mesh(devs, ("i",))
    f = shard_map(lambda x: jax.lax.psum(x, "i"), mesh=mesh,
                  in_specs=P(), out_specs=P(), check_vma=False)
    return f(jnp.ones(()))


def main() -> None:
    ap = argparse.ArgumentParser(
        description="multi-host smoke: init + mesh + one psum barrier")
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args()
    init_distributed(args.coordinator, args.num_processes, args.process_id)
    print(f"process {jax.process_index()}/{jax.process_count()} sees "
          f"{jax.device_count()} devices ({jax.local_device_count()} local)")
    print("barrier psum:", float(_psum_one()))


if __name__ == "__main__":
    main()
