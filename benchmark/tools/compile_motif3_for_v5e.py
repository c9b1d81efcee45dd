#!/usr/bin/env python3
"""Ask the v5e compiler, from a sandbox with no chip, whether the serve
programs of the motif-3-beta configuration fit beside its weights:
`compile_dots3_for_v5e.py` (decode_step and prefill_chunk_paged at the
smallest and the largest admit batch, compiled for a DESCRIBED v5e:2x2
device; nothing runs) for this configuration, at each of `--slots` (64 and
128 unless given). It says what fits, never how fast; the traffic file's
`sizing` records what was found.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_motif3_for_v5e.py [--slots 64] [--chunk 128] [--only check]
"""

import runpy
import sys
from pathlib import Path

if __name__ == "__main__":
    given = sys.argv[1:]
    tool = str(Path(__file__).with_name("compile_dots3_for_v5e.py"))
    sizes = [[]] if "--slots" in given else [["--slots", "64"], ["--slots", "128"]]
    for slots in sizes:
        sys.argv[1:] = ["--config", "motif-3-beta", *slots, *given]
        try:
            runpy.run_path(tool, run_name="__main__")
        except Exception as e:  # a size the compiler refuses is an answer: say so and try the next
            print({"slots": slots or given, "refused": f"{type(e).__name__}: {str(e)[:300]}"}, flush=True)
