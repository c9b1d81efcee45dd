#!/usr/bin/env python
"""Recipe 4: pipeline-parallel training.

TPU-native twin of reference `main-pipe.py` (which does not run as written —
syntax errors at main-pipe.py:63-64,72; SURVEY §2.9 — so this implements its
documented intent). The reference builds an `nn.Sequential` of stages pinned
to successive GPUs, embeddings on the first stage and norm+lm_head on the
last (main-pipe.py:52-77), wraps it in GPipe-style `Pipe(chunks=num_stages)`
(main-pipe.py:79-83) over single-process TensorPipe RPC (main-pipe.py:21-28).

Here the pipeline is a `shard_map` over a `stage` mesh axis: stacked layer
parameters shard across stages, `lax.ppermute` (XLA collective-permute over
ICI) moves activations + the threaded mask/targets stage-to-stage, and a
`lax.scan` runs the micro-batch schedule — no RPC, no wrapper modules, and
the backward comes from autodiff instead of Pipe's hand-built one. The
stage count defaults to the device count (twin of
`num_stages = torch.cuda.device_count()`, main-pipe.py:93) and micro-batch
count equals stage count (`chunks=num_stages`, main-pipe.py:83).

Interleaved virtual stages (round 22): `--pipeline_schedule 1f1b
--virtual_stages V` splits each device's layer block into V non-contiguous
chunks (device d owns chunks d, d+S, ..., d+(V-1)S), shrinking the
warm-up/cool-down bubble toward (S-1)/(M*V) at the same micro-batch count
(`tpukit/pipeline_schedule.py` counts it; no chip run has timed it). MoE rides along: `--num_experts 8
--moe_dispatch pallas` runs the meshless dropless dispatch inside each
stage's chunks — the buffer dispatches ('xla'/'a2a') need an expert mesh
axis the pipeline does not carry and are rejected by name.

Run: `python main-pipe.py --batch_size 64 --num_layers 8 ...`
(num_layers must divide by the stage count).
"""

from tpukit.flags import parse_flags
from tpukit.pipeline import Pipeline, Pipeline1F1B
from tpukit.train import fit


def main(argv=None):
    flags = parse_flags(
        argv, pipeline_schedule=True, num_experts=True, default_experts=0
    )
    cls = Pipeline1F1B if flags.pipeline_schedule == "1f1b" else Pipeline
    # 4x micro-batches per stage shrink the GPipe bubble (divergence from
    # the reference's chunks=num_stages; --microbatches N restores it)
    return fit(
        flags,
        cls(
            num_microbatches=flags.microbatches or "4x",
            moe_dispatch=flags.moe_dispatch if flags.num_experts else None,
        ),
    )


if __name__ == "__main__":
    import sys

    from tpukit.recovery import run_recipe

    # Exit-code contract (docs/DESIGN.md "recovery", README): 0 clean,
    # 75 preempted-and-checkpointed, 76 anomaly abort, 77 rollback budget
    # exhausted — what a babysitter script keys its relaunch decision on.
    sys.exit(run_recipe(main))
