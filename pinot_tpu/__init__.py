"""pinot_tpu — a TPU-native real-time distributed OLAP framework.

Capabilities modeled on Apache Pinot (reference: /root/reference), redesigned
idiomatically for TPUs: columnar segments live as pytrees of device arrays,
per-segment query execution (predicate masks -> projection -> transform ->
aggregate/group-by) compiles to fused XLA programs, per-segment partials merge
via ICI collectives inside shard_map, and SQL planning / routing / ingestion /
cluster management stay host-side.

Layer map (mirrors SURVEY.md L0-L10):
  common/   - schema, config, types              (ref: pinot-spi)
  segment/  - columnar format, dictionaries,
              stats, builder, loader             (ref: pinot-segment-spi/-local)
  query/    - SQL parser, context, planner,
              per-segment engine, reduce         (ref: pinot-core query engine)
  parallel/ - device mesh, sharded combine       (ref: combine/scatter-gather)
"""

import os
from pathlib import Path

import jax

# Pinot semantics require LONG/DOUBLE (64-bit) columns and accumulators.
# JAX defaults to 32-bit; enable x64 unless explicitly disabled. The TPU
# compiler emulates f64/i64 in software, so 64-bit stays the default; the
# storage-level dtype policy (lossless i64->i32 narrowing, opt-in lossy
# fast32) lives in segment.py to_device / QueryEngine(fast32=...).
if os.environ.get("PINOT_TPU_NO_X64", "0") != "1":
    jax.config.update("jax_enable_x64", True)

# Persistent compile cache, placed from outside: JAX reads
# JAX_COMPILATION_CACHE_DIR itself, and only when it is unset does the package
# name a directory — one fixed path in the checkout (the path is part of the
# cache key's context, so it must not move between runs). Every process that
# imports the package (server, datagen, benches) shares it. The compile-time
# floor drops to zero so the many sub-second programs of a cold start are
# cached too, not only the multi-second fused per-plan ones.
COMPILE_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR")
if not COMPILE_CACHE_DIR:
    COMPILE_CACHE_DIR = str(Path(__file__).resolve().parent.parent / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def select_cpu() -> None:
    """Make the CPU the only platform this process may initialise. The
    persistent cache serves the accelerator and goes off with it: XLA:CPU
    loads a cached program as ahead-of-time code without checking it against
    the host's CPU (it says so on every hit), and CPU compiles are cheap."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)


if jax.config.jax_platforms == "cpu":  # JAX_PLATFORMS=cpu in the environment
    select_cpu()


def force_cpu_backend(n_devices: int | None = None) -> None:
    """Select the CPU platform, optionally with N virtual host devices (the
    multi-chip sharding paths then compile and run without hardware). Must run
    before any jax client exists. Shared by tests/conftest.py and
    __graft_entry__.dryrun_multichip; setting JAX_PLATFORMS=cpu in the
    environment selects the platform just as well."""
    import re

    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m:
            if int(m.group(1)) < n_devices:
                flags = flags.replace(
                    m.group(0), f"--xla_force_host_platform_device_count={n_devices}"
                )
                os.environ["XLA_FLAGS"] = flags
        else:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    select_cpu()


__version__ = "0.1.0"
