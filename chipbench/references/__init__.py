"""Plain references: each architecture's forward pass in straightforward
float32 `jax.numpy`, with no kernel, cache or batching, run in a CPU child
outside the measured window and compared with the served answers."""
