"""Smoke run on the chip: serve a model at its published widths through the
BRAVO-leased paged engine, and check what it served against the dense path.

    python chip_smoke.py              # minicpm-2b, one chip
    python chip_smoke.py --chips 4    # granite-20b (bf16 weights), (1, 4) mesh

Everything runs in this one process (a child could not reach the chip its
parent holds).  Weights are random, generated on the device from ``--seed``.
The run drives the engine's scheduler mode through its public entry points:
chunked prefill of prompts of a few hundred to ~1k tokens, a shared prompt
prefix (prefix-cache hit, copy-on-write, refcounts), greedy decode, one
weight hot swap (model-lease revocation) and one compaction that scrubs a
leaked allocation (KV-stripe revocation) while requests are in flight.

Checks: the compiled decode / prefill / lease-publish programs call the
Pallas kernels as Mosaic custom calls (nothing interpreted); every request
returns all its tokens; the paged first-token logits agree with the dense
forward within a stated tolerance; greedy tokens agree except where the
dense run's top-2 margin is inside that tolerance.  One chip also re-serves
two requests from an int8 KV pool and checks them the same way.

Without a TPU the script exits nonzero before doing any work.  The last
line of a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import re
import sys
import time
from pathlib import Path
from typing import Callable, List, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402

from repro import configs  # noqa: E402
from repro.dist.sharding import MeshRules, param_specs  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.common import ModelConfig  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402
from repro.serving.scheduler import SchedulerConfig  # noqa: E402
from repro.serving.steps import jit_step, make_decode_step  # noqa: E402

# Logit tolerances, in logits (random-init logits here have unit scale:
# unit-RMS final hidden state against a 1/sqrt(d) embedding).  The paged
# kernels and the dense path round differently at every layer (online vs
# full softmax, bf16 outputs); one bf16 ulp (2^-8 relative) per layer,
# random-walking over 40-52 layers, moves the final hidden state by about
# sqrt(40) * 2^-8 ~ 2.5%, i.e. logit errors of std ~0.025 whose maximum over
# a ~1e5 vocabulary is ~4.5 std ~ 0.11.  Twice that:
BF16_TOL = 0.25
# An int8 page with a per-(page, head) scale has a step of max|x|/127 ~
# 3.3 std/127, a rounding error of ~0.75% of the activation scale per
# element against bf16's ~0.2-0.4%: about three times the bf16 budget.
INT8_TOL = 0.75

LEAK_RID = 1 << 30        # a request id that never runs: its pages leak
PAGE_SIZE = 16
HBM_RESERVE = 2 << 30     # activations, logits, lease tables, allocator slack


@dataclasses.dataclass(frozen=True)
class Traffic:
    """What the run serves.  Request 1 repeats request 0's prompt (full-page
    prefix hits, then a copy-on-write boundary page: coverage stops one
    token short of the prompt); request 2 shares request 0's first
    ``shared`` tokens and diverges after them."""
    prompt_lens: Sequence[int]
    shared: int
    max_new: int
    sched: SchedulerConfig
    quant_requests: int        # requests re-served from an int8 KV pool


ONE_CHIP = Traffic(
    prompt_lens=(1000, 1000, 900, 700, 520, 384, 640, 300), shared=600,
    max_new=32, quant_requests=2,
    sched=SchedulerConfig(max_slots=8, page_size=PAGE_SIZE, max_seq=1056,
                          prefill_chunk=256, prefill_rows=4,
                          token_budget=1024))
FOUR_CHIPS = dataclasses.replace(ONE_CHIP, quant_requests=0)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
                         f"nothing was run")
    return dev


def kernel_calls(hlo_text: str) -> List[str]:
    """Names of the Pallas kernels a compiled program calls as Mosaic
    custom calls (the ``jit`` wrapper each ``pallas_call`` sits in)."""
    return sorted(set(re.findall(
        r"jit\((_\w+_call)\)/pallas_call",
        "\n".join(ln for ln in hlo_text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in ln))))


def require_kernels(program: str, hlo_text: str, want: Sequence[str]) -> None:
    got = kernel_calls(hlo_text)
    log(f"{program}: Mosaic kernels {got}")
    missing = [k for k in want if k not in got]
    if missing:
        raise AssertionError(f"{program} does not call {missing} as a "
                             f"tpu_custom_call (got {got})")


def pool_pages(devices: Sequence[jax.Device], page_bytes: int,
               want: int) -> int:
    """Pages the KV store gets: ``want`` (half again what the traffic can
    hold at once) unless HBM is shorter.  The store must fit TWICE beside
    what is already resident — a step's layer scan writes its updated
    store into a second buffer before the donated one is freed
    (``memory_analysis`` temp ~ the store's size) — plus a reserve."""
    free = min(d.memory_stats()["bytes_limit"]
               - d.memory_stats()["bytes_in_use"] for d in devices)
    fit = (free - HBM_RESERVE) // (2 * page_bytes)
    if fit < want * 2 // 3:
        raise RuntimeError(f"HBM fits {fit} KV pages of {page_bytes} B; the "
                           f"traffic holds up to {want * 2 // 3} at once")
    return int(min(fit, want))


def peak_bytes(devices: Sequence[jax.Device]):
    stats = [d.memory_stats() for d in devices]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


def init_params(cfg: ModelConfig, rules: MeshRules, mesh: Mesh, seed: int):
    """Random weights made on the device, each leaf in its serving layout
    (nothing is built on the host or on one device first)."""
    init = lambda key: M.init_params(key, cfg)   # noqa: E731
    shapes = jax.eval_shape(init, jax.random.PRNGKey(seed))
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         param_specs(shapes, rules, mesh, decode=True))
    t0 = time.perf_counter()
    params = jax.jit(init, out_shardings=shard)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"params: {n} ({n / 1e9:.3f} B), {nbytes} bytes "
        f"({jnp.dtype(cfg.param_dtype).name}), "
        f"init {time.perf_counter() - t0:.1f}s")
    return params


def make_prompts(cfg: ModelConfig, traffic: Traffic,
                 seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in traffic.prompt_lens]
    prompts[1] = prompts[0].copy()
    prompts[2][:traffic.shared] = prompts[0][:traffic.shared]
    return prompts


def top2_margin(logits: np.ndarray) -> float:
    a, b = np.partition(logits, -2)[-2:]
    return float(abs(b - a))


def dense_reference(cfg, rules, mesh, params, prompts, max_new: int,
                    max_seq: int) -> List[dict]:
    """The dense path, one request at a time: ``M.forward`` over the prompt
    (right-padded to one length, so one compile; causal attention keeps the
    padding out of every real position), then ``init_caches``-layout decode
    steps.  -> per request: first-token logits, greedy tokens, and the top-2
    margin of the logits each token was drawn from."""
    pad_to = max(len(p) for p in prompts)

    @jax.jit
    def prefill(p, tokens, last):
        logits, _, caches = M.forward(p, cfg, {"tokens": tokens}, mesh=mesh,
                                      rules=rules)
        caches = jax.tree.map(
            lambda c: jnp.pad(c, ((0, 0), (0, 0), (0, max_seq - pad_to),
                                  (0, 0), (0, 0))), caches)
        return logits[0, last].astype(jnp.float32), caches

    decode = jit_step(make_decode_step(cfg, mesh, rules), donate_argnums=(1,))
    out = []
    t0 = time.perf_counter()
    for prompt in prompts:
        s = len(prompt)
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :s] = prompt
        first, caches = prefill(params, toks, s - 1)
        logits = np.asarray(first)
        rec = {"first_logits": logits, "tokens": [int(np.argmax(logits))],
               "margins": [top2_margin(logits)]}
        for j in range(max_new - 1):
            cur = np.asarray([[rec["tokens"][-1]]], np.int32)
            _, lg, caches = decode(params, caches, cur,
                                   np.asarray([s + j + 1], np.int32))
            lg = np.asarray(lg[0], np.float32)
            rec["tokens"].append(int(np.argmax(lg)))
            rec["margins"].append(top2_margin(lg))
        out.append(rec)
        del caches
    log(f"dense reference: {len(prompts)} requests x {max_new} tokens in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    return out


def wait_until(cond: Callable[[], bool], eng: ServingEngine,
               timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        eng.check_health()
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out after {timeout_s}s waiting for "
                               f"{what}")
        time.sleep(0.05)


def serve(cfg, rules, mesh, params, prompts, traffic: Traffic, *,
          quant_kv: bool, rids: Sequence[int]) -> dict:
    """One engine lifetime: build, compile, serve ``rids`` (with the
    prefix-sharing arrival order, a hot swap and a compaction), stop."""
    sc = traffic.sched
    kv_bytes_per_token = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(jax.eval_shape(
            lambda: M.init_paged_caches(cfg, 1, 1, quantized=quant_kv))))
    page_bytes = kv_bytes_per_token * sc.page_size
    need = sum(sc.pages_for(len(prompts[i]) + traffic.max_new) for i in rids)
    want = need + need // 2
    n_pages = pool_pages(mesh.devices.flat, page_bytes, want)
    log(f"{'int8' if quant_kv else 'bf16'} KV pool: {n_pages} pages x "
        f"{page_bytes} B = {n_pages * page_bytes} bytes "
        f"({kv_bytes_per_token} B/token)")

    eng = ServingEngine(cfg, params, mesh=mesh, rules=rules, n_pages=n_pages,
                        scheduler=sc, quant_kv=quant_kv)
    suffix = "_quant" if quant_kv else ""
    for step, (secs, compiled) in eng.compile_steps().items():
        log(f"compile {step}{suffix} step: {secs:.1f}s")
        require_kernels(f"{step}{suffix} step", compiled.as_text(),
                        [f"_paged_attn{suffix}_call" if step == "decode"
                         else f"_chunk_attn{suffix}_call"])
    rid_vec = jnp.arange(sc.max_slots, dtype=jnp.int32)
    require_kernels("lease publish", eng.registry.lower_acquire_by_index(
        jnp.zeros_like(rid_vec), rid_vec).compile().as_text(),
        ["_fused_publish_multi_call"])

    # a leaked allocation for the compaction to find and scrub
    if not eng.pages.allocate(LEAK_RID, 2):
        raise RuntimeError("the leaked allocation found no pages")
    reqs = {i: Request(rid=i, prompt=prompts[i], max_new=traffic.max_new,
                       keep_first_logits=True) for i in rids}
    t0 = time.perf_counter()
    eng.start()
    try:
        first, rest = rids[0], list(rids[1:])
        eng.submit(reqs[first])
        # the sharers arrive once request 0's prompt pages are published
        wait_until(lambda: eng.stats.tokens_out >= 1, eng, 600,
                   "the first request's prefill")
        for i in rest:
            eng.submit(reqs[i])
        if not eng.hot_swap(new_params=eng.store.params):
            raise RuntimeError("hot swap abandoned after its retries")
        eng.request_compaction()
        wait_until(lambda: all(r.done.is_set() for r in reqs.values()), eng,
                   900, "every request")
    finally:
        eng.stop()
    wall = time.perf_counter() - t0
    st = eng.lock_stats()
    pool = st["kv_pool"]
    out = {"outs": {i: list(r.out) for i, r in reqs.items()},
           "first_logits": {i: r.first_logits for i, r in reqs.items()},
           "model_lane_revocations": int(
               eng.registry.revocations[eng.store.leases.idx]),
           "stripe_revocations": int(sum(
               eng.registry.revocations[h.idx] for h in eng.kv_pool.locks))}
    e = st["engine"]
    log(f"served {len(reqs)} requests in {wall:.1f}s: tokens per request "
        f"{[len(out['outs'][i]) for i in rids]}, decode steps "
        f"{e['decode_steps']}, prefill ticks {e['prefills']}")
    log(f"host BRAVO model lock: {st['model']['fast_acquires']} fast / "
        f"{st['model']['slow_acquires']} slow acquires; device leases: "
        f"{st['device_leases']['publishes']} fused publishes, "
        f"{st['device_leases']['revocations']} revocations (model lane "
        f"{out['model_lane_revocations']}, KV stripes "
        f"{out['stripe_revocations']}), "
        f"{st['device_leases']['drain_timeouts']} drain timeouts")
    log(f"weight swaps {e['weight_swaps']} (failures {e['swap_failures']}), "
        f"compactions {e['compactions']}; prefix cache: pages saved "
        f"{e['pages_saved']}, COW copies {e['cow_copies']}, cached tokens "
        f"{e['cached_tokens']}; pool free {pool['free']}/{n_pages}, "
        f"refcounts {pool['refcount_total']}")
    checks = {
        "every request returned all its tokens": all(
            len(out["outs"][i]) == traffic.max_new for i in rids),
        "a weight swap landed": e["weight_swaps"] >= 1
        and e["swap_failures"] == 0 and out["model_lane_revocations"] >= 1,
        "a compaction ran and scrubbed the leaked pages":
            e["compactions"] >= 1 and pool["free"] == n_pages,
        "refcounts drained": pool["refcount_total"] == 0,
    }
    if 1 in rids:
        checks["the shared prefix rode the cache"] = (
            e["pages_saved"] >= 1 and e["cow_copies"] >= 1)
    failed = [k for k, ok in checks.items() if not ok]
    if failed:
        raise AssertionError(f"serving checks failed: {failed}")
    del eng
    gc.collect()
    return out


def compare(label: str, served: dict, dense: List[dict], rids, tol: float):
    """First-token logits within ``tol``; greedy tokens equal up to the
    first step whose dense top-2 margin is inside ``tol`` (past a legitimate
    near-tie the two continuations no longer share a context)."""
    worst = 0.0
    for i in rids:
        d = np.abs(served["first_logits"][i] - dense[i]["first_logits"])
        worst = max(worst, float(d.max()))
        got, want = served["outs"][i], dense[i]["tokens"]
        for t, (a, b) in enumerate(zip(got, want)):
            if a != b:
                m = dense[i]["margins"][t]
                if m >= tol:
                    raise AssertionError(
                        f"{label} request {i}: token {t} is {a}, dense says "
                        f"{b} with top-2 margin {m:.4f} >= {tol}")
                log(f"{label} request {i}: tokens agree up to {t}, then a "
                    f"near-tie (dense margin {m:.4f} < {tol})")
                break
    log(f"{label} vs dense: first-token logits max |delta| {worst:.5f} "
        f"(tolerance {tol})")
    if not worst <= tol:
        raise AssertionError(f"{label} first-token logits differ from the "
                             f"dense forward by {worst} > {tol}")


def run(cfg: ModelConfig, rules: MeshRules, mesh: Mesh, traffic: Traffic,
        seed: int) -> None:
    """Every phase; raises on the first that fails."""
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads ({cfg.n_kv_heads} KV) x {cfg.hd}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}; mesh {dict(mesh.shape)}; serving "
        f"in scheduler mode (continuous batching, paged KV, page size "
        f"{traffic.sched.page_size})")
    params = init_params(cfg, rules, mesh, seed)
    prompts = make_prompts(cfg, traffic, seed)
    log(f"prompts: {[len(p) for p in prompts]} tokens, {traffic.max_new} new "
        f"each; request 1 repeats request 0, request 2 shares its first "
        f"{traffic.shared} tokens")
    dense = dense_reference(cfg, rules, mesh, params, prompts,
                            traffic.max_new, traffic.sched.max_seq)
    rids = list(range(len(prompts)))
    served = serve(cfg, rules, mesh, params, prompts, traffic,
                   quant_kv=False, rids=rids)
    compare("bf16 pool", served, dense, rids, BF16_TOL)
    if traffic.quant_requests:
        q_rids = rids[:traffic.quant_requests]
        served = serve(cfg, rules, mesh, params, prompts, traffic,
                       quant_kv=True, rids=q_rids)
        compare("int8 pool", served, dense, q_rids, INT8_TOL)
    log(f"peak bytes in use per device: {peak_bytes(mesh.devices.flat)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_tpu()
    devices = jax.devices()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} devices")
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    if args.chips == 1:
        cfg, rules, _ = configs.get("minicpm-2b")
        traffic = ONE_CHIP
    else:
        cfg, rules, _ = configs.get("granite-20b")
        # f32 weights (81 GB) do not fit 4 x 16 GB; bf16 (40.6 GB) does
        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
        log("granite-20b with bf16 weights (the published f32 would be "
            "81 GB against 64 GB of HBM)")
        traffic = FOUR_CHIPS
    mesh = Mesh(np.array(devices[:args.chips]).reshape(1, args.chips),
                ("data", "model"))
    run(cfg, rules, mesh, traffic, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
