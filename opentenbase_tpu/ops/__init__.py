"""Device kernels: vectorized, static-shape JAX implementations of the
executor operators (the reference's src/backend/executor node set, rebuilt
batch-at-a-time for the MXU/VPU instead of tuple-at-a-time Volcano C).

x64 is enabled at import: SQL int8/decimal/timestamp columns are 64-bit and
aggregate sums overflow 32-bit accumulators at TPC-H scale. On TPU, XLA
emulates i64 with i32 pairs; the perf-critical reductions get specialized
narrower paths in the Pallas kernels, not here.
"""

import jax as _jax

_jax.config.update("jax_enable_x64", True)

# WHERE HOST-SIDE ARRAYS LIVE — the one decision, made here. Everything
# that does not place itself on the device mesh runs on the CPU backend:
# the host executor (executor/local.py and the eager jnp in ops/), DML,
# system views, the coordinator merge. The device path places itself
# explicitly — DeviceCache uploads with a NamedSharding over the mesh
# and every fused program is a shard_map over that mesh — so it is
# untouched by this default. On the chip the alternative (JAX's own
# default, the TPU) makes every INSERT, point read and view read compile
# for and dispatch to the TPU in 64-bit emulation and re-upload its
# numpy inputs per op. A process whose JAX_PLATFORMS excludes "cpu"
# fails loudly at its first host-side op instead of quietly staying on
# the TPU.
_jax.config.update("jax_default_device", "cpu")
