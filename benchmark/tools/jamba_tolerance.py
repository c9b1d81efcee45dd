#!/usr/bin/env python3
"""The control of the jamba2-3b comparison, on the chip: the float32
reference (benchmark/reference/jamba_block.py) against ITSELF computed in a
lower precision, on the set-up check's own seeded sequence, handed to
`modes/serve_latent.py`'s `judge` with the configuration's `tolerance`, the
comparison that decides the cell's `correct`. Three readings: every matmul
operand rounded to bfloat16 (has to pass), to float8_e4m3fn (the nearest
precision below the configuration's: has to be REFUSED), and the scan's state
rounded to bfloat16 after every step with float32 matmuls (told: the reading
behind the float32 state of `assumed.ssm_state`). The exit code is 1 if bf16
does not pass or fp8 is not refused.

    python3 benchmark/tools/jamba_tolerance.py [--seed N] [--prompt_tokens 8192] [--decode_steps 64]
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
    ap.add_argument("--config", default="jamba2-3b")
    ap.add_argument("--seed", type=int, default=3600000019)
    ap.add_argument("--prompt_tokens", type=int, default=8192)
    ap.add_argument("--decode_steps", type=int, default=64)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from tpukit.model import hybrid

    config = common.load_json(ROOT / "benchmark" / "configs" / f"{args.config}.json")
    cfg = hybrid.config_from_hf(config, compute_dtype=config["program"]["compute_dtype"],
                                param_dtype=config["program"]["param_dtype"])
    ref = common.load_by_name("reference", config["reference"], ROOT)
    mode = common.load_by_name("modes", "serve_latent", ROOT)
    params = jax.block_until_ready(jax.jit(lambda k: hybrid.init_params(k, cfg))(common.prng_key(args.seed)))
    tokens = args.prompt_tokens + args.decode_steps
    ids = jnp.asarray(np.random.default_rng(args.seed).integers(0, cfg.vocab_size, size=tokens).astype(np.int32))
    exact = np.asarray(ref.logits(params, ids, hf=config))
    verdicts = {}
    for name, kw in (("bfloat16", {"round_to": jnp.bfloat16}), ("float8_e4m3fn", {"round_to": jnp.float8_e4m3fn}),
                     ("state_bfloat16", {"state_round_to": jnp.bfloat16})):
        low = np.asarray(ref.logits(params, ids, hf=config, **kw))
        verdicts[name], report = mode.judge(low, [], exact, [], prompt_tokens=args.prompt_tokens, topk=0,
                                            tolerance=config["tolerance"])
        print(json.dumps({"reference_rounded_to": name, "seed": args.seed, "ok": verdicts[name], **report,
                          "argmax_kept": float(np.mean(low.argmax(-1) == exact.argmax(-1)))}), flush=True)
    return 0 if (verdicts["bfloat16"], verdicts["float8_e4m3fn"]) == (True, False) else 1


if __name__ == "__main__":
    raise SystemExit(main())
