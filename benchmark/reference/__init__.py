"""Plain references: each family's forward pass in float32 `jax.numpy`, with
`jax.default_matmul_precision("highest")`, no kernels, no remat, no sharding,
written from the published description with the configuration file's listed
departures applied. They import nothing from `horovod_tpu`: a change to the
program cannot change what it is compared with."""
