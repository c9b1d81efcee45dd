#!/usr/bin/env python3
"""Ask the v5e compiler, from a sandbox with no chip, whether a cell's
programs fit: compiles them at the real size for a DESCRIBED v5e:2x2 device
(nothing runs) and prints the compiler's memory figures and the kernels and
collectives in each module. It says what fits, never how fast.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_for_v5e.py --workload <name> \
        [--rows-per-chip N] [--slots N] [--scan-layers 0|1]

The overrides try a size without editing the cell's files.
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
            "output_gb": gb(m.output_size_in_bytes), "alias_gb": gb(m.alias_size_in_bytes)}


def _with_sharding(shapes, shardings):
    import jax

    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), shapes, shardings)


def train(cfg, traffic, chips, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpukit import shardings
    from tpukit.mesh import create_mesh
    from tpukit.obs.xla import collective_bytes, kernel_calls
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    strategy = getattr(shardings, traffic["strategy"])(create_mesh(traffic["mesh"], devices=devices[:chips]))
    optimizer = make_optimizer(traffic["learning_rate"])
    init_fn = lambda rng: create_train_state(rng, cfg, optimizer, strategy)  # noqa: E731
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    train_step, _, state_sharding = make_step_fns(cfg, optimizer, strategy, shapes)
    rows, seq = traffic["rows_per_chip"] * chips, traffic["row_tokens"] - 1
    bsh = strategy.batch_sharding()
    arr = lambda dt: jax.ShapeDtypeStruct((rows, seq), dt, sharding=bsh)  # noqa: E731
    batch = {"input_ids": arr(jnp.int32), "position_ids": arr(jnp.int32), "mask": arr(np.bool_)}
    t0 = time.perf_counter()
    compiled = train_step.lower(_with_sharding(shapes, state_sharding), batch, arr(jnp.int32)).compile()
    text = compiled.as_text()
    print({"program": "train_step", "global_rows": rows, "seq": seq, "compile_s": round(time.perf_counter() - t0, 1),
           **_mem(compiled), "kernels": kernel_calls(text),
           "collectives": {k: v for k, v in collective_bytes(text).items() if v}})
    # the system's half of the reference comparison, on the check's own rows (the
    # reference walks its layers on the host and compiles one layer at a time)
    from benchmark.modes import train as train_mode

    crow = traffic["check_rows_per_chip"] * chips
    carr = lambda dt: jax.ShapeDtypeStruct((crow, seq), dt, sharding=bsh)  # noqa: E731
    cbatch = {"input_ids": carr(jnp.int32), "position_ids": carr(jnp.int32), "mask": carr(np.bool_)}

    def system(p, b, t):
        loss, grads = strategy.value_and_grad(p, cfg, b, t)
        return loss, train_mode._global_norm(grads)

    t0 = time.perf_counter()
    c = jax.jit(system, in_shardings=(state_sharding.params, bsh, bsh)).lower(
        _with_sharding(shapes.params, state_sharding.params), cbatch, carr(jnp.int32)).compile()
    print({"program": "check_system", "rows": crow, "compile_s": round(time.perf_counter() - t0, 1), **_mem(c)})


def serve(cfg, traffic, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from tpukit.model import gpt
    from tpukit.serve import decode, paged
    from tpukit.serve.engine import ServeConfig

    eng = traffic["engine"]
    sv = ServeConfig(slots=eng["slots"], buckets=tuple(eng["buckets"]), max_len=eng["max_len"],
                     max_new_tokens=traffic["output_len"]["max"], decode_quantum=eng["decode_quantum"],
                     page_size=eng["page_size"], kv_dtype=eng["kv_dtype"], prefill_chunk=eng["prefill_chunk"])
    one = SingleDeviceSharding(devices[0])
    on = lambda tree: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)  # noqa: E731
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = on(jax.eval_shape(lambda: gpt.init_params(jax.random.PRNGKey(0), cfg)))
    n, num_pages = sv.slots, sv.slots * sv.pages_per_slot + 1
    cache = on(jax.eval_shape(lambda: paged.init_paged_cache(cfg, num_pages, sv.page_size, sv.pages_per_slot, n, sv.kv_dtype)))
    state = (sds((n, sv.padded_width), jnp.int32), cache, sds((n,), jnp.int32), sds((n,), np.bool_),
             sds((n,), jnp.int32), sds((n, 2), jnp.uint32))
    print({"slots": n, "num_pages": num_pages,
           "kv_pool_gb": round(paged.pool_bytes(cfg, num_pages, sv.page_size, sv.kv_dtype) / 1e9, 3)})
    t0 = time.perf_counter()
    c = decode.decode_step.lower(params, cfg, *state, traffic["eos_id"], 0.0, 0, None, steps=sv.decode_quantum).compile()
    print({"program": "decode_step", "compile_s": round(time.perf_counter() - t0, 1), **_mem(c)})
    for a in (1, sv.slots):
        t0 = time.perf_counter()
        c = decode.prefill_chunk_paged.lower(
            params, cfg, *state, sds((a,), jnp.int32), sds((a, sv.chunk), jnp.int32), sds((a,), jnp.int32),
            sds((a,), np.bool_), sds((a,), jnp.int32), sds((a,), jnp.int32), sds((a, 2), jnp.uint32)).compile()
        print({"program": f"prefill_chunk_paged[{a}x{sv.chunk}]", "compile_s": round(time.perf_counter() - t0, 1), **_mem(c)})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows-per-chip", type=int)
    ap.add_argument("--slots", type=int)
    ap.add_argument("--scan-layers", type=int, choices=(0, 1))
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    from benchmark import common
    from benchmark.run import find_cell
    from tpukit.ops import pallas_attention

    jax.config.update("jax_enable_compilation_cache", False)  # a TPU executable cannot be read back here
    pallas_attention.on_tpu_backend = lambda: True  # jax.devices() is the CPU here; compile the chip's path
    bench = common.load_json(ROOT / "BENCHMARK.json")
    cell, entry = find_cell(bench, args.workload)
    config = common.load_json(ROOT / entry["file"])
    traffic = common.load_json(ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json")
    if args.rows_per_chip:
        traffic["rows_per_chip"] = args.rows_per_chip
    if args.slots:
        traffic["engine"]["slots"] = args.slots
    if args.scan_layers is not None:
        config["program"]["scan_layers"] = bool(args.scan_layers)
    cfg = common.gpt_config(config)
    devices = list(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices)
    if traffic["mode"] == "train":
        train(cfg, traffic, cell["chips"], devices)
    else:
        serve(cfg, traffic, devices)


if __name__ == "__main__":
    main()
