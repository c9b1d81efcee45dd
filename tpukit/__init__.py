"""tpukit — a TPU-native distributed-training cookbook framework.

A ground-up JAX / XLA / pjit / Pallas re-design with the capabilities of the
reference cookbook (`vvvm23/distributed-pytorch-cookbook`): one GPT-style
decoder LM, one data pipeline, and five parallelism recipes (single-device,
data-parallel, fully-sharded, pipeline, pipeline x data-parallel) whose only
difference is the sharding strategy.

Unlike the reference — where parallelism is a model *wrapper* (DDP/FSDP/Pipe)
around an imperative torch module — tpukit expresses the model as a pure
function over a parameter pytree and expresses every parallelism strategy as a
`jax.sharding.Mesh` plus a set of `NamedSharding` rules (or, for the pipeline,
a `shard_map` + `lax.ppermute` schedule). XLA emits the collectives over ICI;
there is no NCCL, no process-group string, no RPC layer.
"""

__version__ = "0.1.0"

import os as _os

# Distributed-without-a-cluster: TPUKIT_CPU_DEVICES=N forces the CPU platform
# with N virtual devices so every mesh strategy (DP/FSDP/pipeline/2-D) can be
# driven from the recipe CLIs on one machine. Must happen before the first
# jax backend use.
_cpu_devices = _os.environ.get("TPUKIT_CPU_DEVICES")
if _cpu_devices:
    import jax as _jax

    _jax.config.update("jax_platforms", "cpu")
    _jax.config.update("jax_num_cpu_devices", int(_cpu_devices))

from tpukit.model import GPTConfig, TransformerDecoderLM  # noqa: F401
