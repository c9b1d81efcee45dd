#!/usr/bin/env python3
"""The control of the deepseek-v3 comparison, on the chip: `dots3_tolerance.py`
(the float32 reference against ITSELF with every matmul operand rounded to
bfloat16, then to float8_e4m3fn, handed to `modes/serve_latent.py`'s `judge`
with the configuration's `tolerance`; it has to pass bfloat16 and REFUSE
float8) for this configuration and its reference
(benchmark/reference/deepseek_v3_block.py), at the set-up check's length: a
document of 20,480 tokens and a question of 128 judged as the prompt, 128
ticks after them. No layer selects keys, so the judged parts are the two
logit errors.

    python3 benchmark/tools/deepseek_v3_tolerance.py [--seed N] [--prompt_tokens 20608] [--decode_steps 128]
"""

import runpy
import sys
from pathlib import Path

if __name__ == "__main__":
    given = sys.argv[1:]
    sys.argv[1:] = ["--config", "deepseek-v3", "--prompt_tokens", "20608", "--decode_steps", "128", *given]
    runpy.run_path(str(Path(__file__).with_name("dots3_tolerance.py")), run_name="__main__")
