"""CPU rehearsal of chip_smoke.py at toy sizes (slow tier).

`python chip_smoke.py` refuses to run without a TPU, so its control flow —
paths, arguments, what each phase reads from the recipes — is rehearsed here
through the same phase functions: interpret-mode kernels, the real recipes
through main(argv), four of the eight virtual devices for `--multichip`.
`compiled=False` drops only the assertions that a kernel is a
`tpu_custom_call`; tests/test_chip_compile.py asks the chip's compiler that.
Run it before spending chip time on the smoke:

    JAX_PLATFORMS=cpu python -m pytest tests/test_chip_smoke_rehearsal.py -m slow
"""

import jax
import pytest

import chip_smoke

pytestmark = pytest.mark.slow

TINY = chip_smoke.Sizes(
    dim=64, heads=4, head_dim=16, layers=4, seq=64, batch=8, vocab=300,
    flash_seqs=(64, 160), flash_batch=2, head_tokens=200,
    moe_shapes=((100, 32, 64, 4), (64, 32, 64, 2)),
    page=16, pages_per_slot=3, paged_slots=4,  # 16 x 16 = one int8 quant block
    train_rows=256, learning_rate=1e-3, steps=3,
    requests=8, slots=4, max_new_tokens=8, buckets="16,32",
)


def test_one_chip_phases(tmp_path, capsys):
    chip_smoke.run_phase("kernels", chip_smoke.phase_kernels, TINY, False)
    params, cfg, ckpt = chip_smoke.run_phase(
        "train", chip_smoke.phase_train, TINY, tmp_path, False
    )
    assert ckpt.is_relative_to(tmp_path)  # nothing written outside the out dir
    chip_smoke.run_phase("full_width", chip_smoke.phase_full_width, TINY, False)
    chip_smoke.run_phase(
        "serve", chip_smoke.phase_serve, TINY, tmp_path, params, cfg, ckpt
    )
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith('{"phase"')]
    assert [chip_smoke.json.loads(l)["phase"] for l in lines] == [
        "kernels", "train", "full_width", "serve",
    ]


def test_multichip_phase():
    chip_smoke.run_phase(
        "multichip", chip_smoke.phase_multichip, TINY, False, jax.devices()[:4]
    )


def test_command_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) == 2
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert chip_smoke.json.loads(last)["ok"] is False
