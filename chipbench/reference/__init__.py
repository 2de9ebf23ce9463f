"""Plain references: each architecture's forward pass in straightforward
`jax.numpy`, float32, `default_matmul_precision("highest")`, no kernels, no
cache, no capacity. They read the program's parameter tree (the same
weights) and share no other code with it."""
