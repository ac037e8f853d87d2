"""Plain references: straight ``jax.numpy``, float32, highest matmul
precision, no kernels, no cache, no batching tricks. They read the program's
parameter trees (same seeded weights) and share no code with it."""
