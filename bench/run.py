"""The chip benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process per run: it finds the cell in ``BENCHMARK.json``, refuses to
run without the TPU chips the cell asks for, makes the weights and the
traffic from ``--seed``, sets up and warms the engine, serves the schedule
open-loop, measures the window, checks what the window served against the
float32 reference, and prints one JSON object as the last line of its
standard output.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from the
program's spans and counters and from a device trace of the window's
middle.  The numbers compared for ``correct`` are printed beside their
limits as the last lines of standard error and under ``limits``, the last
key of the result.
"""

from __future__ import annotations

import time

T_START = time.monotonic_ns()           # process start, for setup_s

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import device, spec, work  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    timeline: object
    dims: work.Dims
    peaks: dict
    chips: int
    profile: object = None        # bench.profile.Reduced, or None


def _have(run) -> dict:
    """Every end-to-end number a run can give, by name."""
    tl = run.timeline
    return {"ttft_p50_ms": lambda: tl.ttft_ms(0.5),
            "tpot_p50_ms": lambda: tl.tpot_ms(0.5),
            "output_tok_s": tl.output_tok_s, "setup_s": lambda: run.setup_s}


def end_to_end(cell, run) -> dict:
    have = _have(run)
    out = {}
    for m in cell.end_to_end:
        v = float(have[m["name"]]())
        if math.isfinite(v):          # a tail of misses is no number: the
            out[m["name"]] = {"value": v, "unit": m["unit"]}   # run fails
    return out


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def measure(cell, seed: int, seconds: float, trace: bool, devices, *,
            peaks: dict) -> dict:
    """One run -> the result object (every key but ``device``'s names)."""
    import jax
    from bench import profile, serve
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        run = serve.serve(cell, seed, seconds, devices, trace_dir=trace_dir,
                          t_process0=T_START, log=log)
        late = serve.lateness(run)
        print(f"generator lateness (submit - due) over {late['n']} window "
              f"requests: p50 {late['p50_ms']} ms, max {late['max_ms']} ms",
              flush=True)
        tl = run.timeline
        print(json.dumps({"window_requests": len(tl.reqs),
                          "window_tokens": tl.output_tokens(),
                          **{k: f() for k, f in _have(run).items()
                             if k != "setup_s"}}), flush=True)
        dims = work.Dims.of(cell.config["model"])
        res = {"correct": False, "attempted": len(run.timeline.reqs),
               "failed": len(run.timeline.failed())}
        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(jax.devices()),
               "memory_peak_bytes": run.memory_peak}
        if trace:
            red = profile.reduce_run(trace_dir, run)
            ctx = Context(run.timeline, dims, peaks, run.chips, red)
            res["metrics"] = per_layer(cell, ctx)
            if red is not None:
                dev["busy_s"] = red.busy_s
                dev["window_s"] = red.window_s
                res["breakdown"] = red.breakdown
        else:
            res["metrics"] = end_to_end(cell, run)
        res["device"] = dev
        chk = serve.check(run, log)
        res["correct"] = chk["correct"]
        if run.compiles:
            log(f"compiled inside the window: {run.compiles}")
        res["limits"] = {k: {"value": v["value"] if math.isfinite(
            v["value"]) else None, "limit": v["limit"]}
            for k, v in chk["limits"].items()}
        return res
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax
    devices = device.require_chips(jax.devices(), cell.chips)
    peaks = device.peaks(devices[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"{cell.name}: {devices[0].device_kind} x {len(devices)} of "
        f"{len(jax.devices())}; jax {jax.__version__}; compile cache {cache}")
    res = measure(cell, args.seed, args.seconds, bool(args.trace), devices,
                  peaks=peaks)
    for k, v in res["limits"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    log(f"correct: {res['correct']}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
