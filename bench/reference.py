"""Plain float32 reference for training cells, and the numbers compared.

Nothing here imports the program.  A configuration's reference module
(``configs/<name>.py``) gives ``init(cfg, key)``, which makes the weights
in the program's parameter layout, and ``loss(params, tokens, labels,
cfg, mm)``, the mean next-token cross-entropy written with plain
``jax.numpy``; every contraction goes through ``mm`` so that the same code
serves as the float32 reference (``exact_mm``) and as its fp8 control
(``fp8_mm``).  ``follow`` drives the first AdamW steps of that reference from
the seed, in blocks of rows and with the optimizer's moments kept on the
host, so that it fits beside nothing on one chip.
"""
from __future__ import annotations

import functools
import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn

# Leaves whose reference gradient is below this share of the median leaf's
# move under Adam by round-off alone; the parameter change leaves them out.
TINY_GRAD = 1e-3


# ------------------------------------------------------------- contractions
def exact_mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _q8(x):
    """Round to float8_e4m3fn under one per-tensor scale, as an fp8 path
    with a tensor-wise amax scale would, and return float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_mm(spec: str, a, b):
    """The control: operands, and in the backward the cotangent, rounded to
    fp8 before a float32 contraction."""
    return exact_mm(spec, _q8(a), _q8(b))


def _fp8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return exact_mm(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, ct):
    _, vjp = jax.vjp(functools.partial(exact_mm, spec), *res)
    return vjp(_q8(ct))


fp8_mm.defvjp(_fp8_fwd, _fp8_bwd)


# ------------------------------------------------------------ shared layers
def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def cross_entropy(x, labels, head, mm, chunk: int = 1024):
    """Mean next-token cross-entropy of rows ``x`` [T, d] against
    ``labels`` [T], with logits ``x @ head.T`` made ``chunk`` rows at a
    time and recomputed in the backward pass."""
    T, d = x.shape
    c = math.gcd(T, chunk)

    @jax.checkpoint
    def one(args):
        xc, lc = args
        logits = mm("td,vd->tv", xc, head)
        picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    parts = jax.lax.map(one, (x.reshape(T // c, c, d),
                              labels.reshape(T // c, c)))
    return jnp.sum(parts) / T


# ------------------------------------------------------------------ leaves
def leaf_names(tree) -> list[str]:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def named_norms(tree) -> dict[str, float]:
    return dict(zip(leaf_names(tree),
                    (float(n) for n in jax.device_get(leaf_norms(tree)))))


def change_from_init(cm, cfg: dict):
    """``fn(params, key)``: per-leaf norms of ``params`` minus the seed's
    weights, which are made inside the same program, so that no second
    copy of the weights is held."""
    @jax.jit
    def norms(params, key):
        start = jax.tree.leaves(cm.init(cfg, key))
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y)))
                for x, y in zip(jax.tree.leaves(params), start)]

    def named(params, key) -> dict[str, float]:
        return dict(zip(leaf_names(params),
                        (float(n) for n in jax.device_get(norms(params, key)))))
    return named


# --------------------------------------------------------------- optimizer
def learning_rate(step: int, opt: dict, total_steps: int) -> float:
    """Warm-up then cosine to a tenth, in float32 as the step computes it."""
    f32 = np.float32
    peak, warm = f32(opt["peak_lr"]), max(int(opt["warmup_steps"]), 1)
    if step < warm:
        return float(peak * f32(step) / f32(warm))
    prog = min(max((step - warm) / max(total_steps - warm, 1), 0.0), 1.0)
    return float(peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog))))


@jax.jit
def _adam_leaf(p, g, m, v, clip, count, lr, b1, b2, eps, wd):
    g = g * clip
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** count)
    vhat = v / (1 - b2 ** count)
    return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(g, s):
    return jax.tree.map(lambda x: x * s, g)


@jax.jit
def _global_norm(g):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))


def _batch_grads(value_and_grad, params, tokens, labels, rows: int):
    """Mean loss and gradient over the batch, ``rows`` rows per call."""
    B = tokens.shape[0]
    rows = max(1, min(rows, B))
    if B % rows:
        rows = 1
    loss, acc = 0.0, None
    for i in range(0, B, rows):
        lo, g = value_and_grad(params, jnp.asarray(tokens[i:i + rows]),
                               jnp.asarray(labels[i:i + rows]))
        loss += float(lo) * rows / B
        g = _scale(g, jnp.float32(rows / B))
        acc = g if acc is None else _add(acc, g)
    return loss, acc


def follow(cm, cfg: dict, opt: dict, key, batches, *, mm=exact_mm,
           total_steps: int, rows_per_call: int) -> dict:
    """Train the reference from the seed's weights over ``batches`` (one
    per step, host arrays) and return what the program is compared on:
    each step's loss, the first clipped gradient's per-leaf norms, and the
    per-leaf norms of the parameters' change after the last step."""
    init = jax.jit(functools.partial(cm.init, cfg))
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(functools.partial(
            cm.loss, cfg=cfg, mm=mm)))
        params = init(key)
        names = leaf_names(params)
        treedef = jax.tree.structure(params)
        m = [np.zeros(x.shape, np.float32) for x in jax.tree.leaves(params)]
        v = [np.zeros(x.shape, np.float32) for x in jax.tree.leaves(params)]
        losses, first = [], None
        f32 = jnp.float32
        for step, batch in enumerate(batches):
            loss, g = _batch_grads(vg, params, batch["tokens"],
                                   batch["labels"], rows_per_call)
            gnorm = float(_global_norm(g))
            clip = min(1.0, opt["grad_clip"] / max(gnorm, 1e-12))
            if step == 0:
                first = {n: x * clip for n, x in named_norms(g).items()}
            lr = learning_rate(step, opt, total_steps)
            new_p = []
            for i, (p, gl) in enumerate(zip(jax.tree.leaves(params),
                                            jax.tree.leaves(g))):
                p2, m2, v2 = _adam_leaf(
                    p, gl, m[i], v[i], f32(clip), f32(step + 1), f32(lr),
                    f32(opt["b1"]), f32(opt["b2"]), f32(opt["eps"]),
                    f32(opt["weight_decay"]))
                m[i], v[i] = np.asarray(m2), np.asarray(v2)
                new_p.append(p2)
            del g, gl, p
            params = jax.tree.unflatten(treedef, new_p)
            del new_p
            losses.append(loss)
        del m, v
        change = change_from_init(cm, cfg)(params, key)
    assert list(first) == names
    return {"losses": losses, "first_grad": first, "change": change}


# ---------------------------------------------------------------- compare
def _worst(values) -> float:
    """The largest value; infinite where any is not finite (a NaN would
    otherwise vanish inside ``max``)."""
    values = list(values)
    return max(values) if all(map(math.isfinite, values)) else math.inf


def gaps(prog: dict, ref: dict) -> dict:
    """The three numbers compared, each taken by the worst step or leaf:

    - ``loss_gap``: |loss - reference| / |reference|, over the steps;
    - ``grad_gap``: per leaf, |norm - reference norm| of the first clipped
      gradient over the larger of the leaf's reference norm and the median
      leaf's;
    - ``update_gap``: the same for the parameters' change after the steps,
      over leaves whose reference gradient is at least ``TINY_GRAD`` of the
      median leaf's.
    """
    loss_gap = _worst(abs(a - b) / abs(b)
                      for a, b in zip(prog["losses"], ref["losses"]))
    g_ref = ref["first_grad"]
    med_g = statistics.median(g_ref.values())
    grad_gap = _worst(abs(prog["first_grad"][n] - r) / max(r, med_g)
                      for n, r in g_ref.items())
    kept = [n for n, r in g_ref.items() if r >= TINY_GRAD * med_g]
    med_c = statistics.median(ref["change"][n] for n in kept)
    update_gap = _worst(abs(prog["change"][n] - ref["change"][n])
                        / max(ref["change"][n], med_c) for n in kept)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap,
            "left_out": sorted(set(g_ref) - set(kept))}
