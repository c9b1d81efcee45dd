"""One tiny CPU rehearsal of each mode through run.py's functions, on a
temporary copy of the benchmark to which a throw-away configuration, traffic
mix, mode, per-layer metric and kernel arithmetic are ADDED as new files and
new BENCHMARK.json entries, with no file that is there edited. The command
itself still refuses the CPU."""

import copy
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run

ROOT = Path(__file__).resolve().parents[2]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 2**31 + 77

TINY_ENGINE = {"slots": 4, "page_size": 8, "kv_dtype": "f32", "prefill_chunk": 16,
               "max_len": 48, "buckets": [16, 32], "decode_quantum": 2}
ECHO_MODE = '''
"""A throw-away mode: no model, a fixed answer, one device op so a trace has something."""


def run(ctx):
    import jax.numpy as jnp

    value = float(jnp.sum(jnp.ones((4,))) * ctx["traffic"]["scale"])
    return {"correct": True, "attempted": 1, "failed": 0,
            "end_to_end": {"echo_value": value, "setup_s": 0.5},
            "record": {"mode": "echo", "cfg": None, "chips": 1, "trace": {"t0": 0.0, "t1": 1.0}, "echo": value}}
'''
ECHO_METRIC = '''
from benchmark import common


def read(rec):
    kernel = common.load_by_name("kernels", "noop", rec["root"])
    return rec["echo"] + kernel.work(rec)["noop"][0]
'''
NOOP_KERNEL = '''
def work(rec):
    return {"noop": (1.0, 2.0)}
'''


def write(path: Path, body) -> None:
    path.write_text(body if isinstance(body, str) else json.dumps(body, indent=1))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(root): p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    original = copy.deepcopy(bench)
    b = root / "benchmark"

    config = json.loads((b / "configs" / "gpt2-medium.json").read_text())
    config.update(name="tiny", n_embd=32, n_head=4, n_layer=2, n_positions=64, vocab_size=97)
    config["program"]["compute_dtype"] = "float32"
    config["tolerance"].update(train_loss_rel=1e-4, train_grad_norm_rel=1e-3)
    write(b / "configs" / "tiny.json", config)
    train = json.loads((b / "traffic" / "train-1k.json").read_text())
    train.update(rows_per_chip=2, row_tokens=64, dataset_rows=16, pad_id=96, trace_seconds=1,
                 lengths={"distribution": "fixed", "value": 64})
    write(b / "traffic" / "tiny-train.json", train)
    write(b / "traffic" / "tiny-fsdp4.json", dict(train, strategy="FSDP", mesh={"data": 4}, check_rows_per_chip=1))
    serve = json.loads((b / "traffic" / "chat-saturate.json").read_text())
    serve.update(requests={"base": 900, "per_second": 0, "block": 30}, eos_id=96, engine=TINY_ENGINE,
                 prompt_len={"distribution": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
                 output_len={"distribution": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 16},
                 ramp={"completions": 6, "seconds": 0.5}, drain_limit_s=30, check_requests=2, trace_seconds=1)
    write(b / "traffic" / "tiny-sat.json", serve)
    write(b / "traffic" / "tiny-open.json", dict(serve, arrivals={"kind": "poisson", "rate_per_s": 20.0}))
    # an open-loop cell is data too: its own end-to-end metrics and a reader of its own
    shutil.copy(b / "layer_metrics" / "dispatch_ms_per_quantum.tput.py", b / "layer_metrics" / "dispatch_ms_per_quantum.ttft.py")
    write(b / "configs" / "throwaway.json", {"name": "throwaway", "source": "none", "reduced": []})
    write(b / "traffic" / "throwaway.json", {"mode": "echo", "why": "a throw-away", "scale": 2.5, "trace_seconds": 1})
    write(b / "modes" / "echo.py", ECHO_MODE)
    write(b / "layer_metrics" / "echo_plus_one.echo.py", ECHO_METRIC)
    write(b / "kernels" / "noop.py", NOOP_KERNEL)

    entry = dict(bench["configs"][0], name="tiny", file="benchmark/configs/tiny.json")
    bench["configs"] += [entry, dict(entry, name="throwaway", file="benchmark/configs/throwaway.json")]
    like = {"tiny.train": ("gpt2-medium.train-1k", "tiny-train", 1), "tiny.fsdp4": ("gpt2-xl.train-fsdp4", "tiny-fsdp4", 4),
            "tiny.sat": ("gpt2-medium.serve-saturate", "tiny-sat", 1), "tiny.open": (None, "tiny-open", 1)}
    for name, (model, traffic, chips) in like.items():
        bench["workloads"].append({"name": name, "config": "tiny", "traffic": traffic, "chips": chips, "why": "rehearsal"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if model in m.get("workloads", []):
                m["workloads"].append(name)
    bench["workloads"].append({"name": "throwaway.echo", "config": "throwaway", "traffic": "throwaway", "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "echo_value", "unit": "1", "better": "higher", "bound": 0.01,
                                "source": "host_clock", "workloads": ["throwaway.echo"]})
    bench["end_to_end"] += [{"name": f"serve_{k}_mean_ms", "unit": "ms", "better": "lower", "bound": 0.05,
                             "source": "host_clock", "workloads": ["tiny.open"]} for k in ("ttft", "tpot")]
    bench["per_layer"].append({"name": "dispatch_ms_per_quantum.ttft", "unit": "ms", "better": "lower", "source": "program_span",
                               "layer": "serve engine", "moves": "serve_ttft_mean_ms", "workloads": ["tiny.open"]})
    bench["per_layer"].append({"name": "echo_plus_one.echo", "unit": "1", "better": "higher", "source": "program_counter",
                               "layer": "echo", "moves": "echo_value", "workloads": ["throwaway.echo"]})
    write(root / "BENCHMARK.json", bench)

    # additions only: every file and every entry that was there is as it was
    for rel, body in before.items():
        assert (root / rel).read_bytes() == body
    for key in ("configs", "workloads"):
        assert bench[key][: len(original[key])] == original[key]
    return root


def cell(root, name, trace=False, seconds=1.0):
    return run.run_cell(name, SEED, seconds, trace, root=root, require_tpu=False)


def test_a_thrown_in_cell_runs_from_new_files_alone(tiny_root):
    out = cell(tiny_root, "throwaway.echo")
    assert set(out) == RESULT_KEYS
    assert out["metrics"] == {"echo_value": {"value": 10.0, "unit": "1"}, "setup_s": {"value": 0.5, "unit": "s"}}
    traced = cell(tiny_root, "throwaway.echo", trace=True)
    assert traced["metrics"] == {"echo_plus_one.echo": {"value": 11.0, "unit": "1"}}


@pytest.mark.parametrize("name", ["tiny.train", "tiny.fsdp4"])
def test_training_mode_rehearsal(tiny_root, name, capsys):
    out = cell(tiny_root, name)
    assert set(out) == RESULT_KEYS and out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert out["metrics"]["train_tokens_per_s_chip"]["value"] > 0 and out["attempted"] >= 3
    assert out["device"]["platform"] == "cpu"  # and so never a device number: see the command's refusal below
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    check = next(x for x in lines if x["info"] == "reference_check")
    assert check["ok"] and check["loss_rel_err"] <= check["loss_rel_tol"]
    window = next(x for x in lines if x["info"] == "window")
    assert window["compiled_in_window"] == 0 and window["loss_fell"]
    program = next(x for x in lines if x["info"] == "program")
    assert program["compiler_total_bytes"] > 0 and "collectives" not in program  # the HLO text is read in traced runs only


@pytest.mark.parametrize("name", ["tiny.train", "tiny.fsdp4"])
def test_training_mode_traced_rehearsal_reports_per_layer_metrics_only(tiny_root, name, capsys):
    out = cell(tiny_root, name, trace=True)
    assert out["correct"] is True
    assert {"input_wait_ms.train", "hbm_program_gb.train"} <= set(out["metrics"])
    assert "train_tokens_per_s_chip" not in out["metrics"]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    program = next(x for x in lines if x["info"] == "program")
    assert ("all-gather" in program["collectives"]) == (name == "tiny.fsdp4")
    # no TPU: no peaks, no device plane; those readers return nothing and are left out
    assert not {"mfu_pct.train", "device_idle_pct.train", "flash_roofline.train"} & set(out["metrics"])


def test_saturated_serving_rehearsal(tiny_root, capsys):
    out = cell(tiny_root, "tiny.sat", seconds=0.4)
    assert set(out) == RESULT_KEYS and out["correct"] is True
    assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    window = next(x for x in lines if x["info"] == "window")
    assert window["queue_never_empty"] and window["compiled_in_window"] == 0 and window["output_tokens"] > 0
    assert next(x for x in lines if x["info"] == "reference_check")["tokens_checked"] > 0


def test_saturated_serving_traced_rehearsal_reads_the_engines_spans(tiny_root):
    out = cell(tiny_root, "tiny.sat", trace=True, seconds=0.4)
    assert out["correct"] is True and "serve_out_tokens_per_s" not in out["metrics"]
    # no TPU: no device plane, so the trace's readers return nothing; the engine's own spans are read
    assert out["metrics"]["dispatch_ms_per_quantum.tput"]["value"] > 0


def test_open_loop_serving_rehearsal(tiny_root):
    out = cell(tiny_root, "tiny.open", seconds=2.0)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 20
    assert set(out["metrics"]) == {"serve_ttft_mean_ms", "serve_tpot_mean_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_open_loop_traced_rehearsal_reads_the_engines_spans(tiny_root):
    out = cell(tiny_root, "tiny.open", trace=True, seconds=2.0)
    assert set(out["metrics"]) == {"dispatch_ms_per_quantum.ttft"} and out["correct"] is True


def test_an_unknown_cell_is_refused(tiny_root):
    with pytest.raises(SystemExit, match="unknown workload"):
        cell(tiny_root, "no.such.cell")


def test_the_command_refuses_anything_but_a_tpu(capsys):
    with pytest.raises(SystemExit, match="needs a TPU"):
        run.main(["--workload", "gpt2-medium.train-1k", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert '"correct"' not in capsys.readouterr().out


def test_the_command_fails_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the paths: another
    exit code than 0 and no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2-medium.train-1k", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and '"correct"' not in done.stdout
