"""Plain references: each architecture's forward pass in straightforward
float32 ``jax.numpy``, written from the published equations, with no kernel,
no cache and no batching, sharing no code with the program's model file."""
