"""Plain references: each architecture's forward pass (and, for training,
its loss, gradients and optimizer rule) in straightforward ``jax.numpy``,
float32, ``default_matmul_precision("highest")``, no kernels, no cache.
Nothing here imports the program under test or takes anything it made."""
