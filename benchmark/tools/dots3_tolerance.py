#!/usr/bin/env python3
"""The control of the dots3-note-prev comparison, on the chip: the float32
reference against ITSELF computed in a lower precision (every matmul operand
rounded to bfloat16, then to float8_e4m3fn: the nearest precision below the
one the configuration states), on the set-up check's own seeded prompt, handed
to `modes/serve_latent.py`'s `judge` with the configuration's `tolerance`: the
comparison that decides the cell's `correct`, part by part. It has to pass
bfloat16 and REFUSE float8 (`"ok": false`); the exit code is 1 if it does
not. Prints what the mode's `setup_check` line prints.

    python3 benchmark/tools/dots3_tolerance.py [--seed N] [--prompt_tokens 4096] [--decode_steps 64]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="dots3-note-prev")
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--prompt_tokens", type=int, default=4096)
    ap.add_argument("--decode_steps", type=int, default=64)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from tpukit.model import latent

    config = common.load_json(ROOT / "benchmark" / "configs" / f"{args.config}.json")
    cfg = latent.config_from_hf(config, compute_dtype=config["program"]["compute_dtype"],
                                param_dtype=config["program"]["param_dtype"])
    ref = common.load_by_name("reference", config["reference"], ROOT)
    mode = common.load_by_name("modes", "serve_latent", ROOT)
    params = jax.block_until_ready(jax.jit(lambda k: latent.init_params(k, cfg))(common.prng_key(args.seed)))
    tokens = args.prompt_tokens + args.decode_steps
    ids = jnp.asarray(np.random.default_rng(args.seed).integers(0, cfg.vocab_size, size=tokens).astype(np.int32))
    exact_sel: list = []
    exact = np.asarray(ref.logits(params, ids, hf=config, selected=exact_sel))
    verdicts = {}
    for name, dtype in (("bfloat16", jnp.bfloat16), ("float8_e4m3fn", jnp.float8_e4m3fn)):
        sel: list = []
        low = np.asarray(ref.logits(params, ids, hf=config, round_to=dtype, selected=sel))
        verdicts[name], report = mode.judge(low, sel, exact, exact_sel, prompt_tokens=args.prompt_tokens,
                                            topk=cfg.index_topk, tolerance=config["tolerance"])
        print(json.dumps({"reference_rounded_to": name, "seed": args.seed, "ok": verdicts[name], **report,
                          "argmax_kept": float(np.mean(low.argmax(-1) == exact.argmax(-1)))}), flush=True)
    return 0 if verdicts == {"bfloat16": True, "float8_e4m3fn": False} else 1


if __name__ == "__main__":
    raise SystemExit(main())
