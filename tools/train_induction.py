"""Train-and-checkpoint a tiny induction target for the spec serve-smoke.

Speculation is an optimization exactly when the target's next tokens are
predictable; a random-init target accepts ~nothing and the
`--min_accept_rate` CI gate would be unpassable (or vacuous). This tool
puts a checkpoint in the regime structured/templated serving traffic
puts a real model in: it trains the SAME tiled-phrase rows the
`repetitive` stream profile generates (`induction_train` below) and saves a
standard tpukit checkpoint that `main-serve.py --checkpoint` restores
params-only, so the CI lane exercises the real cold-start path:

    python tools/train_induction.py --dim 64 --num_layers 2 \
        --steps 400 --out ckpt_induction
    python main-serve.py --dim 64 --num_layers 2 \
        --checkpoint "$(ls -d ckpt_induction/checkpoint-step*)" \
        --draft ngram --stream_profile repetitive ...

Shape flags MUST match the serving invocation's (the params-only reader
verifies structure); `--row_len` must cover the serving position range
(largest bucket + max_new_tokens + spec_k: positions beyond the trained
range decode noise and acceptance collapses).
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def induction_train(cfg, tokenizer, steps, row_len, lr=3e-3, seed=7, batch=8):
    """Train `cfg` on tiled-phrase rows — the `repetitive` stream profile
    as training data — so greedy decode learns induction (continue the
    repetition). Three details are load-bearing: (1) 2+ layers are the
    induction-head minimum; (2) `row_len` must cover the SERVING position
    range (prompt + decode budget + verify scratch) — position embeddings
    beyond the trained range are noise, and greedy continuations wander
    exactly there (acceptance 0.34 vs 0.85 with the range covered); (3)
    the phrases must come from the DISTRIBUTION the serving stream tiles —
    short heads of the corpus stories, the templated-traffic family — not
    uniform random tokens (acceptance 0.30 with random-token phrases vs
    0.99 in-domain): greedy continuation of a repetition the model has
    never seen the token statistics of is exactly where it wanders. The
    training draws use their own seed, not the stream's — in-domain, not
    memorize-the-eval. Returns (state, final_loss)."""
    import jax
    import numpy as np
    import optax

    from tpukit.data import synthetic_stories
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    # cosine decay to ~0: at a constant lr the greedy loops the acceptance
    # gate depends on stay fragile — the loss bounces around 0.1 and the
    # acceptance rate with it (measured 0.54..0.85 across retrains); a
    # decayed finish converges the induction behavior reproducibly
    strategy = SingleDevice()
    optimizer = make_optimizer(optax.cosine_decay_schedule(lr, steps))
    state = create_train_state(
        jax.random.PRNGKey(0), cfg, optimizer, strategy=strategy
    )
    step_fn, _, state_sharding = make_step_fns(
        cfg, optimizer, strategy, jax.eval_shape(lambda: state)
    )
    state = jax.device_put(state, state_sharding)
    rng0 = np.random.RandomState(seed)
    enc = tokenizer(synthetic_stories(128), truncation=True,
                    max_length=8)["input_ids"]
    rows = []
    while len(rows) < 512:
        head = enc[rng0.randint(len(enc))]
        plen = min(int(rng0.randint(2, 5)), len(head))
        if plen < 2:
            continue
        phrase = np.asarray(head[:plen], np.int32)
        rows.append(np.tile(phrase, -(-(row_len + 1) // plen))[: row_len + 1])
    data = np.asarray(rows, np.int32)
    pos = np.ascontiguousarray(np.broadcast_to(
        np.arange(row_len, dtype=np.int32), (batch, row_len)))
    rng = np.random.RandomState(0)
    for _ in range(steps):
        idx = rng.randint(0, len(data), size=batch)
        mb = {"input_ids": data[idx, :row_len], "position_ids": pos,
              "mask": np.zeros((batch, row_len), dtype=bool)}
        state, loss = step_fn(state, mb, data[idx, 1 : row_len + 1])
    return state, float(loss)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--head_dim", type=int, default=16)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--num_layers", type=int, default=2)
    ap.add_argument("--sequence_length", type=int, default=128,
                    help="position-table size; must match the serving "
                    "--sequence_length")
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--row_len", type=int, default=40,
                    help="training row length — cover largest bucket + "
                    "max_new_tokens + spec_k of the serving run")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", type=str, default="ckpt_induction")
    flags = ap.parse_args(argv)

    import jax.numpy as jnp

    from tpukit import checkpoint as ckpt_lib
    from tpukit.data import get_tokenizer
    from tpukit.model import GPTConfig

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    cfg = GPTConfig(
        dim=flags.dim, head_dim=flags.head_dim, heads=flags.heads,
        num_layers=flags.num_layers, vocab_size=tokenizer.vocab_size,
        max_position_embeddings=flags.sequence_length,
        compute_dtype=jnp.float32,
    )
    state, loss = induction_train(
        cfg, tokenizer, flags.steps, flags.row_len, lr=flags.lr,
        seed=flags.seed,
    )
    path = ckpt_lib.save_auto(state, flags.out)
    print(f"induction target: loss {loss:.4f} after {flags.steps} steps "
          f"-> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
