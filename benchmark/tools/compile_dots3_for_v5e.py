#!/usr/bin/env python3
"""Ask the v5e compiler, from a sandbox with no chip, whether the latent
family's serve programs fit beside the weights: compiles `decode_step` and
`prefill_chunk_paged` (the smallest and the largest admit batch) at the
configuration's real size for a DESCRIBED v5e:2x2 device (nothing runs) and
prints the compiler's memory figures. It says what fits, never how fast.
`--only check` sizes the comparison's own programs instead
(`modes/serve_latent.py`'s `CheckPath`: the chunk at `--check-lanes` and at one
lane, and the tick over every slot).

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_dots3_for_v5e.py \
        [--config dots3-note-prev] [--slots 32] [--chunk 128] [--max-len 16384] [--num-pages N]

`slots` / `chunk` are tried downwards by hand: the traffic file's `sizing`
records what was found.
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


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    gb = lambda x: round(x / 1e9, 3)  # noqa: E731
    return {"temp_gb": gb(m.temp_size_in_bytes), "argument_gb": gb(m.argument_size_in_bytes),
            "output_gb": gb(m.output_size_in_bytes), "alias_gb": gb(m.alias_size_in_bytes),
            "total_gb": gb(m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
                           - m.alias_size_in_bytes)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="dots3-note-prev")
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--max-len", type=int, default=16384)
    ap.add_argument("--num-pages", type=int, default=0)
    ap.add_argument("--quantum", type=int, default=4)
    ap.add_argument("--only", default="", help="decode | prefill1 | prefillN | check")
    ap.add_argument("--check-lanes", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import common
    from tpukit.model import latent
    from tpukit.serve import decode, paged
    from tpukit.serve.engine import ServeConfig

    jax.config.update("jax_enable_compilation_cache", False)  # a TPU executable cannot be read back here
    config = common.load_json(ROOT / "benchmark" / "configs" / f"{args.config}.json")
    cfg = latent.config_from_hf(config, compute_dtype=config["program"]["compute_dtype"],
                                param_dtype=config["program"]["param_dtype"])
    sv = ServeConfig(slots=args.slots, buckets=(args.max_len,), max_len=args.max_len, max_new_tokens=1024,
                     decode_quantum=args.quantum, page_size=16, kv_dtype="bf16", prefill_chunk=args.chunk,
                     num_pages=args.num_pages)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)  # noqa: E731
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = on(jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg)))
    n = sv.slots
    kinds = latent.page_kinds(cfg, sv.page_size, sv.kv_dtype)
    pages = {k.table: n * k.pages_for(sv.padded_width, sv.page_size) + 1 for k in kinds}
    if args.num_pages:
        pages["bt"] = args.num_pages
    cache = on(jax.eval_shape(lambda: latent.init_paged_cache(cfg, pages, sv.page_size, sv.pages_per_slot, n, sv.kv_dtype)))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    print({"slots": n, "chunk": sv.chunk, "pages": pages, "weights_gb": round(weights / 1e9, 3),
           "parameters": sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)),
           "kv_pool_gb": round(paged.pool_bytes(cfg, pages, sv.page_size, sv.kv_dtype) / 1e9, 3)}, flush=True)
    if args.only == "check":
        return check_programs(args, cfg, params, cache, sds, common)
    state = (sds((n, sv.padded_width), jnp.int32), cache, sds((n,), jnp.int32), sds((n,), np.bool_),
             sds((n,), jnp.int32), sds((n, 2), jnp.uint32))
    if args.only in ("", "decode"):
        t0 = time.perf_counter()
        c = decode.decode_step.lower(params, cfg, *state, cfg.vocab_size, 0.0, 0, None, steps=sv.decode_quantum).compile()
        print({"program": "decode_step", "compile_s": round(time.perf_counter() - t0, 1), **_mem(c)}, flush=True)
    for a, tag in ((1, "prefill1"), (sv.slots, "prefillN")):
        if args.only not in ("", tag):
            continue
        t0 = time.perf_counter()
        c = decode.prefill_chunk_paged.lower(
            params, cfg, *state, sds((a,), jnp.int32), sds((a, sv.chunk), jnp.int32), sds((a,), jnp.int32),
            sds((a,), np.bool_), sds((a,), jnp.int32), sds((a,), jnp.int32), sds((a, 2), jnp.uint32)).compile()
        print({"program": f"prefill_chunk_paged[{a}x{sv.chunk}]", "compile_s": round(time.perf_counter() - t0, 1),
               **_mem(c)}, flush=True)


def check_programs(args, cfg, params, cache, sds, common) -> None:
    import jax.numpy as jnp
    import numpy as np

    mode = common.load_by_name("modes", "serve_latent", ROOT)
    path = mode.CheckPath(cfg, {"slots": args.slots, "page_size": 16, "prefill_chunk": args.chunk,
                                "max_len": args.max_len, "kv_dtype": "bf16"})
    n = args.slots
    for a in (args.check_lanes, 1):
        t0 = time.perf_counter()
        c = path._chunk.lower(params, cache, sds((a,), jnp.int32), sds((a, args.chunk), jnp.int32),
                              sds((a,), jnp.int32), sds((a,), jnp.int32)).compile()
        print({"program": f"check chunk[{a}x{args.chunk}]", "compile_s": round(time.perf_counter() - t0, 1),
               **_mem(c)}, flush=True)
    t0 = time.perf_counter()
    c = path._tick.lower(params, cache, sds((n,), jnp.int32), sds((n,), jnp.int32), sds((n,), np.bool_),
                         sds((), jnp.int32)).compile()
    print({"program": f"check tick[{n}]", "compile_s": round(time.perf_counter() - t0, 1), **_mem(c)}, flush=True)


if __name__ == "__main__":
    main()
