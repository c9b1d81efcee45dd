"""Test harness configuration.

Distributed-without-a-cluster (SURVEY §4): force the CPU platform with 8
virtual devices so every mesh strategy (DP, FSDP sharding, pipeline ppermute,
2-D pipe x DP) is testable on one process with bit-level assertions. Must run
before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

# Belt and braces: if anything imported jax before this conftest, the env
# var alone is too late, but the config flag still wins as long as no
# backend has been initialized yet.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)

# Persistent compile cache: repeat test runs skip recompilation.
from tpukit.cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from tpukit.model import GPTConfig, init_params  # noqa: E402


@pytest.fixture(scope="session")
def tiny_config():
    """GPT-tiny in float32 for exact-math tests."""
    import jax.numpy as jnp

    return GPTConfig(
        dim=32,
        head_dim=8,
        heads=4,
        num_layers=2,
        vocab_size=97,
        max_position_embeddings=64,
        compute_dtype=jnp.float32,
    )


@pytest.fixture(scope="session")
def tiny_params(tiny_config):
    return init_params(jax.random.PRNGKey(0), tiny_config)


@pytest.fixture()
def rng():
    return np.random.RandomState(1234)


@pytest.fixture()
def fresh_compiles():
    """The persistent compile cache off for one test. Its key leaves the
    metadata out, so a hit hands back the executable of whichever compile
    wrote the entry, with THAT compile's `op_name`s: a test that reads names
    out of a compiled module's text has to compile it itself."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()
