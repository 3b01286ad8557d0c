"""Multi-host bootstrap for real TPU pods.

On-cluster entry point: every host calls ``init_distributed()`` before
any other jax usage (``make_mining_mesh(multihost=True)`` does); the
coordinator address and process indices come from explicit arguments or
the ``COORDINATOR_ADDRESS`` / ``NUM_PROCESSES`` / ``PROCESS_ID``
environment.  After init, ``jax.devices()`` spans the whole slice and
the same ``make_mining_mesh()`` used on one host lays the ``(block,
cls)`` mesh over it.  ``python -m repro.launch.multihost`` is a
smoke test: init, print the device view, one psum barrier.
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

import jax


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Initialise jax.distributed from flags or scheduler environment."""
    coordinator = coordinator or os.environ.get("COORDINATOR_ADDRESS")
    if coordinator is None:
        # single-host run (tests, one chip): nothing to do
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes
                          or os.environ.get("NUM_PROCESSES", 1)),
        process_id=int(process_id or os.environ.get("PROCESS_ID", 0)))


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
