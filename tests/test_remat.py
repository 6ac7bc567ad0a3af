"""``maybe_remat``'s ``full`` policy keeps the flash attention forward's
named outputs, so the backward does not run that forward again; in a
function that names nothing it is bare ``jax.checkpoint``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import layers
from repro.models.attention import chunked_attention, direct_attention
from repro.models.ssm_lm import MambaLM

B, S, D, H, KV, HD, N_LAYERS = 1, 256, 32, 4, 2, 8, 3


def _weights():
    r = np.random.default_rng(0)

    def w(shape, fan_in):
        return jnp.asarray(r.standard_normal((N_LAYERS,) + shape)
                           * fan_in ** -0.5, jnp.float32)

    return {"wq": w((D, H, HD), D), "wk": w((D, KV, HD), D),
            "wv": w((D, KV, HD), D), "wo": w((H, HD, D), H * HD)}


def _x():
    r = np.random.default_rng(1)
    return jnp.asarray(r.standard_normal((B, S, D)), jnp.float32)


def _flash(causal):
    return lambda q, k, v: chunked_attention(q, k, v, causal, q_chunk=64,
                                             kv_chunk=64)


def _direct(causal):
    return lambda q, k, v: direct_attention(q, k, v, causal)


def _grad(wrap, att):
    """jit(grad) of a ``lax.scan`` over ``N_LAYERS`` blocks (projections,
    attention, output projection, residual), each under ``wrap``."""
    def block(x, w):
        q = jnp.einsum("bsd,dhk->bshk", x, w["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, w["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, w["wv"])
        return x + jnp.einsum("bshk,hkd->bsd", att(q, k, v), w["wo"]), None

    def loss(x, ws):
        y, _ = jax.lax.scan(wrap(block), x, ws)
        return jnp.sum(jnp.tanh(y))

    return jax.jit(jax.grad(loss, argnums=(0, 1)))


def _policy(remat):
    return lambda fn: layers.maybe_remat(fn, remat)


WRAPS = {"bare": jax.checkpoint, "full": _policy("full"),
         "none": _policy("none")}


@pytest.mark.parametrize("causal", [True, False])
def test_full_remat_runs_the_flash_forward_once(causal):
    """The flash forward's KV loop body holds two ``exp``s (the tile and
    the running max's rescale), the backward's one.  Bare
    ``jax.checkpoint`` runs the forward again in the backward, with its
    loops; ``full`` compiles to as many ``exp``s as saving everything
    does, and to fewer loops than bare."""
    counts = {}
    for name, wrap in WRAPS.items():
        text = _grad(wrap, _flash(causal)).lower(
            _x(), _weights()).compile().as_text()
        counts[name] = (len(re.findall(r" while\(", text)),
                        len(re.findall(r" exponential\(", text)))
    (loops, exps), (bare_loops, bare_exps) = counts["full"], counts["bare"]
    assert exps == counts["none"][1] == 3
    assert bare_exps - exps == 2
    assert bare_loops - loops >= 2


@pytest.mark.parametrize("causal", [True, False])
def test_full_remat_gradients_equal_bare_checkpoint(causal):
    x, ws = _x(), _weights()
    want = _grad(jax.checkpoint, _flash(causal))(x, ws)
    got = _grad(_policy("full"), _flash(causal))(x, ws)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("causal", [True, False])
def test_full_remat_gradients_match_direct(causal):
    x, ws = _x(), _weights()
    want = _grad(lambda f: f, _direct(causal))(x, ws)
    got = _grad(_policy("full"), _flash(causal))(x, ws)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=3e-3, atol=3e-3)


def test_full_remat_of_an_ssm_names_nothing_and_is_bare_checkpoint(
        monkeypatch):
    """A Mamba2 layer emits neither name: its grad jaxpr under ``full`` is
    bare ``jax.checkpoint``'s, but for the policy parameter itself."""
    model = MambaLM(get_reduced("mamba2-780m"), remat="full")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def grad_jaxpr():
        text = str(jax.make_jaxpr(jax.grad(lambda p, b: model.loss(p, b)[0]))(
            params, {"tokens": tok, "labels": tok}))
        policies = re.findall(r"policy=.*", text)
        return re.sub(r"policy=.*", "policy=", text), policies

    full, full_policies = grad_jaxpr()
    monkeypatch.setattr(layers, "maybe_remat",
                        lambda fn, remat: jax.checkpoint(fn))
    bare, bare_policies = grad_jaxpr()
    assert full == bare
    assert bare_policies == ["policy=None"]
    assert len(full_policies) == 1 and "save_only_these_names" in \
        full_policies[0]
