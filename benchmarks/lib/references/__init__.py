"""Plain references: the published forward pass, loss and optimizer update
in straightforward float32 `jax.numpy` at the highest matmul precision, with
no kernels, no cache and no batching tricks. They import nothing of the
program and take nothing it has made."""
