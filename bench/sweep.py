"""Rate sweep, to find the knee a cell's fixed rate is set from.

    python bench/sweep.py --workload <cell> --seconds 45 --rates 0.1,0.2,0.3

Serves the cell's mix at each session rate in one process (no reference
check) and prints, per rate, the load offered and served, the median and
90th-percentile TTFT and TPOT, and the queue wait of the window's first
and last thirds: a queue that grows through the window means the rate is
past what the system sustains.  The benchmark's own runs never run this.
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
from bench.e2e import nearest_rank  # noqa: E402
from bench.run import log  # noqa: E402


def summary(run, seconds: float) -> dict:
    tl = run.timeline
    n = len(tl.reqs)
    third = max(n // 3, 1)
    q = lambda rs, p: nearest_rank([r.queue_ms() for r in rs], p)  # noqa
    steps = tl.spans_in("decode_step")
    return {"requests": n, "failed": len(tl.failed()),
            "offered_tok_s": sum(r.max_new for r in tl.reqs) / seconds,
            "output_tok_s": tl.output_tok_s(),
            "ttft_p50_ms": tl.ttft_ms(0.5), "ttft_p90_ms": tl.ttft_ms(0.9),
            "tpot_p50_ms": tl.tpot_ms(0.5), "tpot_p90_ms": tl.tpot_ms(0.9),
            "queue_first_third_p50_ms": q(tl.reqs[:third], 0.5),
            "queue_last_third_p50_ms": q(tl.reqs[-third:], 0.5),
            "decode_rows_mean": (sum(s.args.get("batch", 0) for s in steps)
                                 / len(steps)) if steps else None,
            "evictions": sum(r.evictions for r in tl.every)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=4242)
    ap.add_argument("--drain", type=float, default=30.0,
                    help="seconds past the window to wait for its requests")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cell.traffic = dict(cell.traffic, drain_s=args.drain)
    import jax
    devices = device.require_chips(jax.devices(), cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    for rate in (float(r) for r in args.rates.split(",")):
        run = serve.serve(cell, args.seed, args.seconds, devices,
                          trace_dir=None, t_process0=time.monotonic_ns(),
                          log=log, session_rate=rate)
        print(json.dumps({"session_rate_per_s": rate,
                          **summary(run, args.seconds)}), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
