"""A CPU rehearsal of the deepseek-v3 cell (modes/serve_docqa.py) on a tiny
preset: a temporary copy of the benchmark to which a tiny configuration (the
published keys at toy widths: every layer full, YaRN whose original context
the documents pass, 16 experts in 4 routing groups of which 2 are held), a
tiny traffic mix of two documents and a cell are ADDED. One traced and one
untraced `run_cell`: `correct` true (the set-up's logits through a registry
hit, the timed path's tokens, every admission served its whole document),
every counter reader the real cell lists reads a number, and the prefix
reader reads the registry's share; the mode's `judge` refuses the reference
computed a precision below the program's. On a program whose latent family
cannot read the configuration the mode exits at once and says so. The
traffic: every request starts with a whole document, and every block holds
the same sizes. The prefix reader on a worked record."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from benchmark import common, run

ROOT = Path(__file__).resolve().parents[2]
REAL, TINY, SHORT = "deepseek-v3.serve-docqa", "tiny-deepseek.sat", "tiny-deepseek.short-check"
SEED = 2**31 + 40
PREFIX = "prefix_served_pct.tput"


def tiny_config(config: dict) -> dict:
    tiny = dict(config, name="tiny-deepseek")
    tiny.update(
        hidden_size=64, vocab_size=97, intermediate_size=96, moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=32,
        kv_lora_rank=24, n_routed_experts=2, n_group=4, topk_group=2, num_experts_per_tok=4, rope_theta=100,
        rope_scaling=dict(config["rope_scaling"], factor=4, original_max_position_embeddings=16, beta_fast=4),
        published=dict(config["published"], n_routed_experts=16),
        program=dict(config["program"], param_dtype="float32", compute_dtype="float32"),
        tolerance=dict(config["tolerance"], logit_rms_rel=1e-4),
    )
    return tiny


def tiny_traffic(traffic: dict) -> dict:
    return dict(
        traffic, requests={"base": 800, "per_second": 0, "block": 4}, eos_id=96, documents={"tokens": [32, 48]},
        question_len={"distribution": "lognormal", "median": 5, "sigma": 0.5, "min": 2, "max": 8},
        output_len={"distribution": "lognormal", "median": 5, "sigma": 0.5, "min": 3, "max": 8},
        engine={"slots": 4, "page_size": 4, "kv_dtype": "f32", "prefill_chunk": 8, "max_len": 80, "num_pages": 64,
                "buckets": [56], "decode_quantum": 2},
        setup={"question_tokens": 5, "new_tokens": 1}, ramp={"completions": 4},
        setup_check={"doc_tokens": 48, "question_tokens": 8, "decode_steps": 8, "lanes": 2},
        check_documents=[1, 0], check_new_tokens=8, compile_workers=2, trace_seconds=1)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_deepseek")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    b = root / "benchmark"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((b / "configs" / "deepseek-v3.json").read_text())
    (b / "configs" / "tiny-deepseek.json").write_text(json.dumps(tiny_config(config)))
    traffic = json.loads((b / "traffic" / "docqa-saturate.json").read_text())
    (b / "traffic" / "tiny-docqa.json").write_text(json.dumps(tiny_traffic(traffic)))
    short = tiny_traffic(traffic)  # a set-up check that stops before the longest request's last position
    short["setup_check"] = dict(short["setup_check"], decode_steps=2)
    (b / "traffic" / "tiny-docqa-short.json").write_text(json.dumps(short))
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v3")
    bench["configs"].append(dict(entry, name="tiny-deepseek", file="benchmark/configs/tiny-deepseek.json"))
    bench["workloads"].append({"name": TINY, "config": "tiny-deepseek", "traffic": "tiny-docqa", "chips": 1,
                               "why": "rehearsal"})
    bench["workloads"].append({"name": SHORT, "config": "tiny-deepseek", "traffic": "tiny-docqa-short", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(TINY)
    # the prefix reader's entry waits for a `benchmark` PR: this copy lists it
    bench["per_layer"].append({"name": PREFIX, "unit": "%", "better": "higher", "source": "program_counter",
                               "layer": "cache manager", "moves": "serve_out_tokens_per_s", "workloads": [TINY]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def capsys_module():
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        yield buf.getvalue


@pytest.fixture(scope="module")
def traced(tiny_root, capsys_module):
    out = run.run_cell(TINY, SEED, 1.0, True, root=tiny_root, require_tpu=False)
    return out, [json.loads(line) for line in capsys_module().splitlines() if line.startswith('{"info"')]


# the CPU run has no device plane: readers of the device trace find nothing to read there
DEVICE_TRACE = {"decode_tick_device_ms.tput", "device_idle_pct.tput", "prefill_device_ms_per_ktoken.tput",
                "hbm_peak_gb.tput"}


@pytest.mark.parametrize("part", ["correct", "counter_readers", "info_lines"])
def test_the_traced_rehearsal(traced, tiny_root, part):
    out, info = traced
    by = {line["info"]: line for line in info}
    if part == "correct":
        assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    elif part == "counter_readers":
        bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
        listed = {m["name"] for m in bench["per_layer"] if TINY in m.get("workloads", [])}
        assert {"kv_bytes_per_ctx_token.tput", "expert_load_max_over_mean.tput", "slot_occupancy_pct.tput",
                "host_ms_per_quantum.tput", PREFIX} <= listed
        for name in sorted(listed - DEVICE_TRACE):
            assert name in out["metrics"], name
        # every admission's document came from the registry: most of the prompt tokens
        assert 70.0 < out["metrics"][PREFIX]["value"] < 100.0
    else:
        window = by["window"]
        assert window["queue_never_empty"] and window["compiled_in_window"] == 0
        assert window["prefix_hit_rate"] == 1.0 and window["prompt_tokens_from_cache"] > 0
        assert window["whole_documents_served"] and window["documents_tokens_admitted"] > 0
        assert window["prompt_tokens_from_cache_since_setup"] == window["documents_tokens_admitted"]
        assert set(window["counters_at_close"]) == {"expert_rows", "expert_rows_max"}
        assert by["setup"]["registered_pages"] >= (32 + 48) // 4
        assert by["setup_check"]["ok"] and by["setup_check"]["prompt_tokens"] == 56
        # one request on each named document, every token it generated
        assert by["reference_check"]["ok"] and by["reference_check"]["requests"] == 2
        assert 0 < by["reference_check"]["tokens_checked"] <= 16


def test_untraced_run_reports_the_cells_end_to_end_metrics(tiny_root):
    out = run.run_cell(TINY, SEED + 1, 1.0, False, root=tiny_root, require_tpu=False)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}


def test_the_judge_passes_the_programs_precision_and_refuses_the_one_below(tiny_root):
    import jax
    import jax.numpy as jnp

    from tpukit.model import latent

    config = json.loads((tiny_root / "benchmark" / "configs" / "tiny-deepseek.json").read_text())
    mode = common.load_by_name("modes", "serve_latent", tiny_root)
    ref = common.load_by_name("reference", config["reference"], tiny_root)
    cfg = latent.config_from_hf(config, compute_dtype="float32", param_dtype="float32")
    params = latent.init_params(jax.random.PRNGKey(3), cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, size=46).astype(np.int32))
    exact = np.asarray(ref.logits(params, ids, hf=config))
    low = np.asarray(ref.logits(params, ids, hf=config, round_to=jnp.bfloat16))
    kw = dict(prompt_tokens=40, topk=0, tolerance=config["tolerance"])
    assert mode.judge(exact, [], exact, [], **kw)[0]
    ok, report = mode.judge(low, [], exact, [], **kw)
    assert not ok and report["prefill_logit_rms_rel"] > config["tolerance"]["logit_rms_rel"]


def test_on_a_program_that_cannot_read_the_configuration_the_mode_exits_at_once(tiny_root, monkeypatch):
    """As on the parent's tree: its `config_from_hf` reads the dots3 key set
    for a config without `sliding_window_pattern` and asks for `layer_types`."""
    from tpukit.model import latent

    def parents(hf, **kw):
        return hf["layer_types"]

    monkeypatch.setattr(latent, "config_from_hf", parents)
    with pytest.raises(SystemExit, match="cannot build the configuration"):
        run.run_cell(TINY, SEED, 1.0, False, root=tiny_root, require_tpu=False)


def test_a_set_up_check_short_of_the_longest_request_is_refused_at_once(tiny_root):
    with pytest.raises(SystemExit, match="stops short of the 59 positions"):
        run.run_cell(SHORT, SEED, 1.0, False, root=tiny_root, require_tpu=False)


def test_the_reference_names_every_program_its_forward_dispatches(tiny_root, monkeypatch):
    """`lowered_programs` against the calls `logits` makes: the same modules,
    text for text, so the forward finds each one in the cache."""
    import jax
    import jax.numpy as jnp

    from tpukit.model import latent

    config = json.loads((tiny_root / "benchmark" / "configs" / "tiny-deepseek.json").read_text())
    ref = common.load_by_name("reference", config["reference"], tiny_root)
    cfg = latent.config_from_hf(config, compute_dtype="float32", param_dtype="float32")
    params = latent.init_params(jax.random.PRNGKey(5), cfg)
    called = set()

    def recording(fn):
        def call(*args, **kw):
            called.add(fn.lower(*args, **kw).as_text())
            return fn(*args, **kw)
        return call

    for name in ("attention", "gated_ffn", "route", "head", "_rms_jit"):
        monkeypatch.setattr(ref, name, recording(getattr(ref, name)))
    ids = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, size=40).astype(np.int32))
    ref.logits(params, ids, hf=config)
    monkeypatch.undo()
    ahead = [low.as_text() for low in ref.lowered_programs(jax.eval_shape(lambda: params), 40, hf=config)]
    assert set(ahead) == called and len(ahead) == len(called) == 6


def test_every_request_starts_with_a_whole_document_and_blocks_hold_the_same_sizes():
    from benchmark import traffic_docqa

    traffic = json.loads((ROOT / "benchmark" / "traffic" / "docqa-saturate.json").read_text())
    docs = traffic_docqa.documents(dict(traffic, documents={"tokens": [128, 256, 384, 512]}), 16160, SEED)
    tiny = dict(traffic, documents={"tokens": [128, 256, 384, 512]})
    reqs = traffic_docqa.serve_requests(tiny, 16160, SEED, 2.0, docs)
    assert len(reqs) == traffic["requests"]["base"] + 2 * traffic["requests"]["per_second"]
    for r in reqs:
        doc = docs[r["doc"]]
        assert r["ids"][: len(doc)] == doc and len(r["ids"]) > len(doc)
    block = 4
    sizes = lambda rs: sorted((r["doc"], len(r["ids"]) - len(docs[r["doc"]]), r["max_new_tokens"]) for r in rs)  # noqa: E731
    first = sizes(reqs[:block])
    assert all(sizes(reqs[i:i + block]) == first for i in range(0, len(reqs) - block + 1, block))
    assert sorted(d for d, _, _ in first) == [0, 1, 2, 3]  # one question on each document a block
    assert [(q, a) for _, q, a in first] == [(54, 276), (104, 57), (129, 172), (155, 90)]
    other = traffic_docqa.serve_requests(tiny, 16160, SEED + 1, 2.0, docs)
    assert [r["doc"] for r in other] != [r["doc"] for r in reqs] and sizes(other[:block]) == first
    setup = traffic_docqa.setup_requests(tiny, 16160, SEED, docs)
    assert [r["ids"][: len(d)] == d and len(r["ids"]) == len(d) + 32 for r, d in zip(setup, docs)] == [True] * 4
    assert min(r["rid"] for r in setup) > max(r["rid"] for r in reqs)


def test_the_prefix_reader_on_a_worked_record():
    reader = common.load_by_name("layer_metrics", PREFIX, ROOT)
    quanta = [{"prefix_tokens": 0, "admitted_tokens": 0}, {"prefix_tokens": 16384, "admitted_tokens": 16483},
              {"prefix_tokens": 8192 + 20480, "admitted_tokens": 8246 + 20635}]
    assert reader.read({"quanta": quanta}) == pytest.approx(100.0 * 45056 / 45364)
    assert reader.read({"quanta": [{"steps": 4, "delivered": 10}]}) is None  # a program that counts neither
    assert reader.read({"quanta": quanta[:1]}) is None  # nothing admitted


def test_live_lanes_sampled_at_a_sync_are_their_requests_so_far():
    """The tie rule may sample a lane still decoding at the window's close:
    its ids are the request's own prompt (a registry hit leaves the buffer
    row's prefix unwritten) and the tokens delivered so far, which the
    finished completion then starts with."""
    import jax

    from tpukit.model import latent
    from tpukit.serve.engine import Request, ServeConfig, ServeEngine

    mode = common.load_by_name("modes", "serve_docqa", ROOT)
    cfg = latent.tiny_full_config()
    params = latent.init_params(jax.random.PRNGKey(2), cfg)
    sv = ServeConfig(slots=2, buckets=(32,), max_len=48, max_new_tokens=10, page_size=4, kv_dtype="f32",
                     prefill_chunk=8)
    seen = []

    class Watched(ServeEngine):
        def sync(self, now):
            super().sync(now)
            seen.extend(mode.live_completions(self))

    engine = Watched(params, cfg, sv, eos_id=96)
    rng = np.random.default_rng(0)
    doc = tuple(rng.integers(0, 96, size=16).tolist())
    engine.run([Request(rid=9, ids=doc + (1, 2, 3, 4, 5), max_new_tokens=1, seed=0, arrival_s=0.0)])
    comps = engine.run([Request(rid=i, ids=doc + tuple(rng.integers(0, 96, size=3).tolist()), max_new_tokens=10,
                                seed=0, arrival_s=0.0) for i in range(2)])
    done = {c.rid: np.asarray(c.ids) for c in comps}
    live = [c for c in seen if c.rid in done]
    assert live and {c.rid for c in live} == {0, 1}  # both lanes, each through a registry hit
    assert all(c.ids.shape == (c.prompt_len + c.generated,) for c in live)
    for c in live:
        np.testing.assert_array_equal(c.ids, done[c.rid][: len(c.ids)])
