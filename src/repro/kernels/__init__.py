# Pallas kernels of the mining hot path (bitmap intersect/diff, N-list
# merge, slab compaction), their dispatch wrappers (ops.py) and the
# pure-jnp oracles each is pinned to (ref.py).
