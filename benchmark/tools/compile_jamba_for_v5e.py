#!/usr/bin/env python3
"""Ask the v5e compiler, from a sandbox with no chip, whether the jamba2-3b
configuration's serve programs fit beside its weights: compiles
`decode_step` and `prefill_chunk_paged` (the smallest and the largest admit
batch) at the configuration's real size for a DESCRIBED v5e:2x2 device
(nothing runs) and prints the compiler's memory figures. It says what fits,
never how fast. `--only check` sizes the comparison's own programs instead
(`modes/serve_hybrid.py`'s `CheckPath`: the chunk at `--check-lanes` lanes
and the tick over every slot). The traffic file's `sizing` records what was
found; `slots` and `chunk` are tried downwards by hand.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_jamba_for_v5e.py [--slots 128] [--chunk 256] [--only decode]
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="jamba2-3b")
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--max-len", type=int, default=16384)
    ap.add_argument("--quantum", type=int, default=4)
    ap.add_argument("--only", default="", help="decode | prefill1 | prefillN | check")
    ap.add_argument("--check-lanes", type=int, default=32)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import common
    from tpukit.model import hybrid
    from tpukit.serve import decode, paged
    from tpukit.serve.engine import ServeConfig

    compile_tool = common.load_by_name("tools", "compile_dots3_for_v5e", ROOT)
    jax.config.update("jax_enable_compilation_cache", False)  # a TPU executable cannot be read back here
    config = common.load_json(ROOT / "benchmark" / "configs" / f"{args.config}.json")
    cfg = hybrid.config_from_hf(config, compute_dtype=config["program"]["compute_dtype"],
                                param_dtype=config["program"]["param_dtype"])
    sv = ServeConfig(slots=args.slots, buckets=(args.max_len,), max_len=args.max_len, max_new_tokens=1024,
                     decode_quantum=args.quantum, page_size=16, kv_dtype="bf16", prefill_chunk=args.chunk)
    one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    on = lambda tree: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)  # noqa: E731
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = on(jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), cfg)))
    n = sv.slots
    pages = n * sv.pages_per_slot + 1
    cache = on(jax.eval_shape(lambda: hybrid.init_paged_cache(cfg, pages, sv.page_size, sv.pages_per_slot, n,
                                                              sv.kv_dtype)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    print({"slots": n, "chunk": sv.chunk, "pages": pages, "weights_gb": round(weights / 1e9, 3),
           "parameters": sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)),
           "pool_and_state_gb": round(paged.pool_bytes(cfg, pages, sv.page_size, sv.kv_dtype, slots=n) / 1e9, 3)},
          flush=True)
    if args.only == "check":
        mode = common.load_by_name("modes", "serve_hybrid", ROOT)
        path = mode.CheckPath(cfg, {"slots": n, "page_size": 16, "prefill_chunk": args.chunk,
                                    "max_len": args.max_len, "kv_dtype": "bf16"}, shapes_only=True)
        lanes = args.check_lanes
        for name, low in (
                (f"check chunk[{lanes}x{args.chunk}]", path._chunk.lower(
                    params, cache, sds((lanes,), jnp.int32), sds((lanes, args.chunk), jnp.int32),
                    sds((lanes,), jnp.int32), sds((lanes,), jnp.int32))),
                (f"check tick[{n}]", path._tick.lower(params, cache, sds((n,), jnp.int32), sds((n,), jnp.int32),
                                                      sds((n,), np.bool_), sds((), jnp.int32)))):
            t0 = time.perf_counter()
            c = low.compile()
            print({"program": name, "compile_s": round(time.perf_counter() - t0, 1), **compile_tool._mem(c)},
                  flush=True)
        return
    state = (sds((n, sv.padded_width), jnp.int32), cache, sds((n,), jnp.int32), sds((n,), np.bool_),
             sds((n,), jnp.int32), sds((n, 2), jnp.uint32))
    programs = []
    if args.only in ("", "decode"):
        programs.append(("decode_step", lambda: decode.decode_step.lower(
            params, cfg, *state, cfg.vocab_size, 0.0, 0, None, steps=sv.decode_quantum)))
    for a, tag in ((1, "prefill1"), (n, "prefillN")):
        if args.only in ("", tag):
            programs.append((f"prefill_chunk_paged[{a}x{sv.chunk}]", lambda a=a: decode.prefill_chunk_paged.lower(
                params, cfg, *state, sds((a,), jnp.int32), sds((a, sv.chunk), jnp.int32), sds((a,), jnp.int32),
                sds((a,), np.bool_), sds((a,), jnp.int32), sds((a,), jnp.int32), sds((a, 2), jnp.uint32))))
    for name, lower in programs:
        t0 = time.perf_counter()
        low = lower()
        t1 = time.perf_counter()
        c = low.compile()
        print({"program": name, "lower_s": round(t1 - t0, 1), "compile_s": round(time.perf_counter() - t1, 1),
               **compile_tool._mem(c)}, flush=True)


if __name__ == "__main__":
    main()
