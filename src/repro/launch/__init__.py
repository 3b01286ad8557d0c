# Launch layer: the (block, cls) mining mesh, forced CPU device counts
# for multi-device tests, and the multi-host bootstrap.
