"""Replica worker process for the crash-tolerance fleet tests (round 24).

Builds the SAME tiny engine as tests/test_fleet.py's fixtures — identical
tokenizer corpus, GPTConfig and PRNGKey(1) params — so a worker process is
token-identical to the in-test control engine, then serves leases from the
ledger directory until the supervisor publishes stop (or the wall budget
runs out: an orphaned worker must exit, not linger past the test).

Usage: python tests/fleet_worker.py FLEET_DIR REPLICA_IDX
"""

import sys
from pathlib import Path

# the script lives in tests/, so the interpreter puts tests/ (not the repo
# root) on sys.path — put tpukit back in reach however we were launched
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    directory, replica = sys.argv[1], int(sys.argv[2])

    import jax
    import jax.numpy as jnp

    # mirror tests/conftest.py's PRNG + cache config: the control engine's
    # params come from the SAME PRNGKey(1) stream, so the worker must draw
    # with the same threefry flavor or parity is dead on arrival
    jax.config.update("jax_threefry_partitionable", True)

    from tpukit.cache import enable_compilation_cache
    from tpukit.data import WordTokenizer, synthetic_stories
    from tpukit.model import GPTConfig, init_params
    from tpukit.serve import ServeConfig, ServeEngine
    from tpukit.serve.ledger import serve_from_ledger

    enable_compilation_cache()
    tok = WordTokenizer(synthetic_stories(64))
    cfg = GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=tok.vocab_size,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(1), cfg)
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=10,
                        window_steps=8)
    engine = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id),
                         replica=replica)
    comps = serve_from_ledger(engine, directory, replica, max_wall_s=240.0)
    print(f"replica {replica}: served {len(comps)} completions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
