#!/usr/bin/env python3
"""The control of the motif-3-beta comparison, on the chip: `dots3_tolerance.py`
(the float32 reference against ITSELF with every matmul operand rounded to
bfloat16, then to float8_e4m3fn, handed to `modes/serve_latent.py`'s `judge`
with the configuration's `tolerance`; it has to pass bfloat16 and REFUSE
float8) for this configuration and its reference
(benchmark/reference/motif3_block.py). No full layer selects keys here, so
the judged parts are the two logit errors.

    python3 benchmark/tools/motif3_tolerance.py [--seed N] [--prompt_tokens 4096] [--decode_steps 64]
"""

import runpy
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.argv[1:1] = ["--config", "motif-3-beta"]
    runpy.run_path(str(Path(__file__).with_name("dots3_tolerance.py")), run_name="__main__")
