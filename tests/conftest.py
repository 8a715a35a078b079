"""Test env: the CPU platform with 8 virtual devices, so multi-chip sharding
paths compile and execute without TPU hardware (SURVEY environment notes).
The platform is explicit, which is also what puts the Pallas kernels in
interpret mode and leaves the persistent compile cache off (pinot_tpu.select_cpu).
"""

import pinot_tpu  # noqa: F401  (enables x64, must precede jax use)

pinot_tpu.force_cpu_backend(n_devices=8)

import jax  # noqa: E402

assert jax.default_backend() == "cpu", f"tests must run on cpu, got {jax.default_backend()}"
