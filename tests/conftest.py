import jax

# x64 for the numerical-analysis tests (integrators, solvers).
jax.config.update("jax_enable_x64", True)

# NOTE: we deliberately do NOT set xla_force_host_platform_device_count
# here — the tests must see 1 device.  The multi-device tests spawn
# subprocesses with their own XLA_FLAGS.
