"""The check's control, on the chip at a cell's own size.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

For each seed: one run of the cell (a short window at the cell's own load,
long enough to finish and sample as many served tokens as a run does),
then the run's own check (``serve.check``) with the float8 control in the
program's place: over the same sampled prompts and served tokens, the
float32 reference's widest gap for the tokens the control puts first, and
beside it the program's.  The control must come out not correct on every
seed; the program below the limit.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import device, serve, spec  # noqa: E402
from bench.run import log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    devices = device.require_chips(jax.devices(), cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = serve.serve(cell, seed, args.seconds, devices, trace_dir=None,
                          t_process0=time.monotonic_ns(), log=log)
        chk = serve.check(run, log, control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "program_gap": chk["program_gap"],
                          "control_gap":
                              chk["limits"]["widest_logit_gap"]["value"],
                          "control_correct": chk["correct"],
                          "sample": chk["sample"],
                          "limit": cell.config["gap_limit"]}), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
