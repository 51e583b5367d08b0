"""Lowered-step lint applied to every jitted serving step (the CI gate
that the paged serving stack keeps its lowering guarantees: zero host
transfers inside lease-held steps, no dense-KV materialization on paged
steps, donation aliasing where the engine declares it)."""

import pytest

jax = pytest.importorskip("jax")

from repro.analysis import lint_hlo as LH  # noqa: E402

STEP_NAMES = ["prefill", "decode", "decode_paged", "prefill_paged",
              "decode_paged_quant", "prefill_paged_quant", "copy_page"]


@pytest.fixture(scope="module")
def steps():
    return LH.serving_steps()


def test_all_engine_steps_covered(steps):
    assert sorted(steps) == sorted(STEP_NAMES)


@pytest.mark.parametrize("name", STEP_NAMES)
def test_step_lints_clean(steps, name):
    findings = LH.lint_step(name, **steps[name])
    assert findings == [], "\n".join(str(f) for f in findings)


@pytest.mark.parametrize("name", STEP_NAMES)
def test_step_has_zero_transfers(steps, name):
    assert LH.find_transfers(steps[name]["compiled"], name) == []


@pytest.mark.parametrize("name", ["decode_paged", "prefill_paged",
                                  "decode_paged_quant",
                                  "prefill_paged_quant"])
def test_paged_steps_forbid_dense_kv(steps, name):
    # the forbidden shape is real: it's the dense gather the paged
    # kernels replace, so it must be declared...
    assert steps[name]["forbid_shapes"], "paged step declares a dense shape"
    # ...and absent from the lowering
    for dims in steps[name]["forbid_shapes"]:
        assert not LH.find_shape(steps[name]["lowered"], dims)


@pytest.mark.parametrize("name", ["decode_paged", "prefill_paged",
                                  "decode_paged_quant",
                                  "prefill_paged_quant", "copy_page"])
def test_donating_steps_alias(steps, name):
    assert steps[name]["require_donation"]
    assert LH.has_donation(steps[name]["lowered"])


@pytest.mark.parametrize("name", ["decode_paged_quant",
                                  "prefill_paged_quant"])
def test_quant_steps_forbid_fp32_pool(steps, name):
    # the quantized steps declare the fp32 twin of the int8 page store
    # (and its stacked all-layers form) as forbidden...
    assert steps[name]["forbid_fp32_shapes"]
    # ...and the lowering holds neither
    for dims in steps[name]["forbid_fp32_shapes"]:
        assert not LH.find_shape(steps[name]["lowered"], dims, dtype="f32")


def test_dense_reference_would_fail_the_lint():
    """The dense formulation of chunk prefill DOES materialize the
    gathered KV buffer — proves the dense-kv rule has teeth."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref as R

    rng = np.random.default_rng(0)
    b, lanes, ps, kvh, hd, sq = 2, 4, 8, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(b, sq, 4, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(1, 16, ps, kvh * hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(1, 16, ps, kvh * hd)), jnp.float32)
    pi = jnp.asarray(rng.integers(0, 16, size=(b, lanes)), jnp.int32)
    cl = jnp.zeros((b,), jnp.int32)
    nl = jnp.full((b,), sq, jnp.int32)
    lowered = jax.jit(R.paged_chunk_dense_ref).lower(
        q, kp, vp, jnp.int32(0), pi, cl, nl).as_text()
    fs = LH.lint_step("dense_ref", lowered,
                      forbid_shapes=[(b, lanes * ps, kvh, hd)])
    assert [f.rule for f in fs] == ["dense-kv-materialization"]


def test_fp32_materialization_rule_has_teeth():
    """Dequantizing a layer's pages up front DOES build the fp32 twin of
    the int8 page store — proves the fp32-page rule catches exactly the
    shortcut the quant kernels exist to avoid."""
    import jax.numpy as jnp
    from repro.kernels import quant as Q

    n_pages, ps, kvh, hd = 16, 8, 2, 16
    kq = jnp.zeros((n_pages, ps, kvh, hd), jnp.int8)
    ks = jnp.ones((n_pages, kvh), jnp.float32)
    lowered = jax.jit(lambda q, s: Q.dequantize_pages(q, s).reshape(
        n_pages, ps, kvh * hd)).lower(kq, ks).as_text()
    fs = LH.lint_step("deq_all", lowered,
                      forbid_fp32_shapes=[(n_pages, ps, kvh * hd)])
    assert [f.rule for f in fs] == ["fp32-page-materialization"]


@pytest.mark.parametrize("step", ["decode_paged_quant",
                                  "prefill_paged_quant"])
def test_planted_fp32_store_trips_the_lint(steps, step):
    """A quantized step that upcasts the whole lane-dense store to float32
    before running still trips ``fp32-page-materialization`` under the
    shapes the serving-step lint forbids: the guard has not gone silent
    with the store's new layout."""
    import jax.numpy as jnp
    from repro import configs
    from repro.models import model as M

    cfg = configs.get_smoke("llama3.2-1b")
    store = M.init_paged_caches(cfg, 16, 8, quantized=True)

    def upcast(store):
        return {k: x.astype(jnp.float32) * 2 for k, x in store.items()}

    lowered = jax.jit(upcast).lower(store).as_text()
    forbid = steps[step]["forbid_fp32_shapes"]
    assert LH.pool_fp32_shapes(cfg, 16, 8) == forbid
    fs = LH.lint_step("planted", lowered, forbid_fp32_shapes=forbid)
    assert [f.rule for f in fs] == ["fp32-page-materialization"]
