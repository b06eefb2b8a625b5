"""Test harness config: force an 8-device virtual CPU platform.

Multi-device sharding tests exercise the population mesh without TPU pods, per
SURVEY.md §4(c). Must run before jax initializes its backend, hence conftest.
"""

import os

# Hard override: whatever the environment holds (a chip machine defaults to
# the TPU), tests run on the 8-device virtual CPU platform.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Zero-egress environment: HF hub lookups otherwise burn 45-95s per test in
# connection-timeout retries (the encode_prompts/evaluate tests were the
# slowest in the suite purely from this).
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
# Persistent compile cache: JAX CPU compiles dominate test wall-clock otherwise.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
# Numerical parity tests (vs torch reference implementations) need true f32
# matmuls; the platform default is a faster reduced-precision path. Must go
# through jax.config — the env var is not honored on this build.
import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
# A pytest plugin may import jax before this conftest runs, in which case the
# env vars above were read too late — force the platform through the config
# (works until the first backend initialization).
jax.config.update("jax_platforms", "cpu")
# Same for the persistent compile cache (observed: env vars alone leave the
# cache dir empty under pytest because jax is already imported).
jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
