"""The plain reference against the program's own dense forward, and its
float8 control: at the smoke widths on the CPU the control's widest gap
fails the limit that the served tokens pass."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from bench import reference, serve, weights
from bench.tests import tiny
from repro import configs
from repro.dist.sharding import param_specs
from repro.models import model as M


@pytest.fixture(scope="module")
def setup():
    m = tiny.MODEL
    _, rules, _ = configs.get("minicpm-2b")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    params = weights.make(
        m, (lambda s: param_specs(s, rules, mesh, decode=True), mesh), 5)
    return m, rules, mesh, params


def test_reference_matches_the_programs_forward_in_float32(setup):
    m, rules, mesh, params = setup
    cfg = dataclasses.replace(serve.model_config(m),
                              param_dtype=jnp.float32,
                              compute_dtype=jnp.float32)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(1, m["vocab"], 70)
    with jax.default_matmul_precision("highest"):
        want, _, _ = M.forward(p32, cfg, {"tokens": jnp.asarray(toks[None])},
                               mesh=mesh, rules=rules)
    got = reference.forward_logits(params, m, toks, np.arange(70),
                                   block_q=32)
    np.testing.assert_allclose(got, np.asarray(want[0]), atol=2e-4)


def test_control_fails_the_limit(setup):
    """The float8 control, at each position of the same sequences, puts
    first a token whose reference logit lies below the best by more than
    the limit the program's served tokens meet (tied head, as the cell:
    the benchmark's weights keep the input token from winning every
    position)."""
    _, rules, mesh, _ = setup
    m = dict(tiny.MODEL, n_layers=4)
    params = weights.make(
        m, (lambda s: param_specs(s, rules, mesh, decode=True), mesh), 6)
    rng = np.random.default_rng(1)
    sample = []
    for i in range(4):
        prompt = rng.integers(1, m["vocab"], 40)
        out = []
        for _ in range(24):                    # greedy, by the reference
            seq = np.concatenate([prompt, np.asarray(out, np.int64)])
            out.append(int(np.argmax(reference.forward_logits(
                params, m, seq, [len(seq) - 1], block_q=32)[0])))
        sample.append(type("S", (), {"rid": i, "prompt": prompt,
                                     "out": np.asarray(out)})())
    res = reference.compare(params, m, sample, control=True)
    assert res["gap"] == 0.0 and res["tokens"] == 4 * 24
    assert res["control_gap"] > tiny.GAP_LIMIT


def test_widest_gap():
    logits = np.array([[0.0, 2.0, 1.5], [3.0, 0.0, 2.9]])
    assert reference.widest_gap(logits, [1, 0]) == 0.0
    assert reference.widest_gap(logits, [2, 2]) == pytest.approx(0.5)
