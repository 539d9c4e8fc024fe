"""Plain reference of a dense decoder LM's training step.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision: the
forward pass (embedding, RMSNorm, rotary attention with grouped key/value
heads, gated or plain MLP, LM head), the mean next-token cross-entropy, its
gradient, global-norm clipping and AdamW.  It imports nothing of the program
under test: no ``shard_map``, no ring collectives, no Pallas kernels.  Its
only sharding is what ``jax.jit`` places from the shardings it is given.

The math follows the configuration file's ``program`` block, which states
where the program departs from the published model (padded vocabulary in the
softmax, RMSNorm and rotary positions for granite).  Memory is kept low by
blocking: each layer is recomputed in the backward pass, attention runs over
blocks of queries and the loss over blocks of tokens, each recomputed too.

``dt`` rounds every matmul operand, and the cotangent each operand gets
back, to a narrower type under one scale per tensor before a float32
product: ``float8_e4m3fn`` gives the lower-precision control that the
comparison has to reject.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NORM_LEAVES = ("scale", "q_norm", "k_norm")
Q_BLOCK = 512
LOSS_BLOCK = 512


def padded_vocab(m) -> int:
    return (m["vocab_size"] + m["vocab_multiple"] - 1) // m["vocab_multiple"] \
        * m["vocab_multiple"]


def param_shapes(m) -> dict:
    """Nested dict of leaf shapes for the program block ``m`` of a config."""
    H, L, F = m["d_model"], m["num_layers"], m["d_ff"]
    nh, nkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    V = padded_vocab(m)
    attn = {"wq": (L, H, nh * dh), "wk": (L, H, nkv * dh),
            "wv": (L, H, nkv * dh), "wo": (L, nh * dh, H)}
    if m["qk_norm"]:
        attn["q_norm"] = (L, dh)
        attn["k_norm"] = (L, dh)
    mlp = {"w1": (L, H, F), "w2": (L, F, H)}
    if m["mlp_kind"] == "swiglu":
        mlp["w1b"] = (L, H, F)
    shapes = {"embed": {"table": (V, H)}, "final_norm": {"scale": (H,)},
              "blocks": {"norm1": {"scale": (L, H)},
                         "norm2": {"scale": (L, H)},
                         "attn": attn, "mlp": mlp}}
    if not m["tie_embeddings"]:
        shapes["lm_head"] = {"w": (H, V)}
    return shapes


def _paths(shapes, prefix=()):
    for k in sorted(shapes):
        v = shapes[k]
        if isinstance(v, dict):
            yield from _paths(v, prefix + (k,))
        else:
            yield prefix + (k,), tuple(v)


def _set(tree, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def init_params(m, key) -> dict:
    """Weights from ``key``: norm scales 1, embedding and head N(0, 0.02),
    other matrices N(0, 1/fan_in), truncated at 3 sigma, float32.  Leaf i
    draws from ``fold_in(key, i)``, so a leaf is the same whatever else is
    built with it and whatever its sharding."""
    out: dict = {}
    for i, (path, shape) in enumerate(_paths(param_shapes(m))):
        name = path[-1]
        if name in NORM_LEAVES:
            leaf = jnp.ones(shape, jnp.float32)
        else:
            std = (0.02 if name == "table" or path[0] == "lm_head"
                   else 1.0 / math.sqrt(shape[-2]))
            leaf = jax.random.truncated_normal(
                jax.random.fold_in(key, i), -3.0, 3.0, shape,
                jnp.float32) * std
        _set(out, path, leaf)
    return out


def leaves(tree) -> list:
    """Leaves of a params-shaped tree, keys sorted at every level: the
    order of every per-leaf list."""
    out = []
    for k in sorted(tree):
        v = tree[k]
        out.extend(leaves(v) if isinstance(v, dict) else [v])
    return out


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _quant(x, dt):
    """x rounded to ``dt`` under one scale that maps its largest magnitude
    to the type's largest finite value."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dt).max), 1.0)
    return (x / scale).astype(dt).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, dt):
    """Rounds x to ``dt`` going forward and its cotangent going back."""
    return _quant(x, dt)


_round.defvjp(lambda x, dt: (_quant(x, dt), None),
              lambda dt, _, g: (_quant(g, dt),))


def _mm(spec, a, b, dt):
    if dt != jnp.float32:
        a, b = _round(a, dt), _round(b, dt)
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """Split-half rotary embedding of x [B, S, h, D]."""
    d = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(d, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * freqs          # [S, d]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d], x[..., d:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, dt):
    """Causal softmax attention, q/k/v [B, S, nh, dh], over query blocks."""
    B, S, nh, dh = q.shape
    qb = min(Q_BLOCK, S)
    nb = S // qb
    scale = dh ** -0.5

    @jax.checkpoint
    def block(args):
        qi, i = args                                   # [B, qb, nh, dh]
        s = _mm("bqhd,bkhd->bhqk", qi, k, dt) * scale
        qpos = i * qb + jnp.arange(qb)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, dt)

    qs = q.reshape(B, nb, qb, nh, dh).transpose(1, 0, 2, 3, 4)
    o = lax.map(block, (qs, jnp.arange(nb)))
    return o.transpose(1, 0, 2, 3, 4).reshape(B, S, nh, dh)


def _layer(m, dt, x, p):
    B, S, _ = x.shape
    nh, nkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    pos = jnp.arange(S)
    h = _rms(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q = _mm("bsh,ho->bso", h, a["wq"], dt).reshape(B, S, nh, dh)
    k = _mm("bsh,ho->bso", h, a["wk"], dt).reshape(B, S, nkv, dh)
    v = _mm("bsh,ho->bso", h, a["wv"], dt).reshape(B, S, nkv, dh)
    if m["qk_norm"]:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q = _rope(q, pos, m["rope_theta"])
    k = _rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    o = _attention(q, k, v, dt).reshape(B, S, nh * dh)
    x = x + _mm("bso,oh->bsh", o, a["wo"], dt)
    h = _rms(x, p["norm2"]["scale"], eps)
    f = p["mlp"]
    u = _mm("bsh,hf->bsf", h, f["w1"], dt)
    if m["mlp_kind"] == "swiglu":
        u = jax.nn.silu(u) * _mm("bsh,hf->bsf", h, f["w1b"], dt)
    else:
        u = jax.nn.gelu(u, approximate=True)
    return x + _mm("bsf,fh->bsh", u, f["w2"], dt)


def loss(m, params, tokens, labels, dt=jnp.float32, drop_partial=False):
    """Mean next-token cross-entropy of tokens [B, S] against labels [B, S].

    The softmax runs over the padded vocabulary, as the program's does.
    ``drop_partial`` leaves out, in every MLP, the second half of the
    contraction over the feed-forward width: what a 2x2 tile's output
    projection gives when the reduction between its chips is skipped.  It is
    the planted fault "exchange between chips left out"."""
    x = params["embed"]["table"][tokens]
    layer = partial(_layer, m, dt)
    if drop_partial:
        def layer(x, p, _inner=layer):
            F = p["mlp"]["w2"].shape[0]
            keep = (jnp.arange(F) < F // 2).astype(jnp.float32)[:, None]
            p = {**p, "mlp": {**p["mlp"], "w2": p["mlp"]["w2"] * keep}}
            return _inner(x, p)
    x, _ = lax.scan(lambda c, p: (jax.checkpoint(layer)(c, p), None), x,
                    params["blocks"])
    x = _rms(x, params["final_norm"]["scale"], m["norm_eps"])
    w = (params["embed"]["table"].T if m["tie_embeddings"]
         else params["lm_head"]["w"])
    B, S, H = x.shape
    tb = min(LOSS_BLOCK, S)
    xs = x.reshape(B, S // tb, tb, H).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, S // tb, tb).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk(acc, args):
        xc, lc = args
        lg = _mm("bth,hv->btv", xc, w, dt)
        mx = lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(lg - mx), axis=-1)) + mx[..., 0]
        gold = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    total, _ = lax.scan(chunk, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (B * S)


# ---------------------------------------------------------------------------
# the optimizer and three steps
# ---------------------------------------------------------------------------

def lr_at(opt, step: int) -> float:
    """Warm-up then cosine learning rate at 0-based ``step``."""
    warm = min(1.0, (step + 1) / max(1, opt["warmup_steps"]))
    prog = min(max((step - opt["warmup_steps"])
                   / max(1, opt["total_steps"] - opt["warmup_steps"]), 0.0),
               1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (0.1 + 0.9 * cos)


def _adamw(opt, step, lr, p, g, mu, nu):
    b1, b2 = opt["beta1"], opt["beta2"]
    t = step + 1
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    mh = mu / (1 - b1 ** t)
    vh = nu / (1 - b2 ** t)
    p = p - lr * (mh / (jnp.sqrt(vh) + 1e-8) + opt["weight_decay"] * p)
    return p, mu, nu


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves(tree)])


def _constrain(tree, shardings):
    if shardings is None:
        return tree
    return jax.lax.with_sharding_constraint(tree, shardings)


class Reference:
    """Runs the reference's first steps of a cell on ``shardings``' devices.

    ``shardings`` maps a leaf shape to the sharding its arrays take, or is
    None for one device.  ``batch_fn(i)`` gives step i's (tokens, labels)."""

    def __init__(self, m, opt, *, dt=jnp.float32, shardings=None,
                 drop_partial=False):
        self.m, self.opt = m, opt
        shapes = param_shapes(m)
        psh = None
        if shardings is not None:
            psh = jax.tree.map(shardings, shapes,
                               is_leaf=lambda s: isinstance(s, tuple))
        self._init = jax.jit(partial(init_params, m), out_shardings=psh)
        clip = opt["grad_clip"]

        def grad_step(params, tokens, labels):
            val, g = jax.value_and_grad(loss, argnums=1)(
                m, params, tokens, labels, dt, drop_partial)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves(g)))
            scale = jnp.minimum(1.0, clip / (gn + 1e-6))
            g = jax.tree.map(lambda x: x * scale, g)
            return val, g

        self._grad = jax.jit(grad_step, out_shardings=(None, psh))
        def update(step, lr, p, g, mu, nu):
            out = jax.tree.map(partial(_adamw, opt, step, lr), p, g, mu, nu)
            return tuple(jax.tree.map(lambda o, k=k: o[k], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
                         for k in range(3))

        self._update = jax.jit(update, donate_argnums=(2, 4, 5),
                               out_shardings=(psh, psh, psh))
        self._zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                              out_shardings=psh)
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(
            lambda p, key: leaf_norms(jax.tree.map(
                lambda a, b: a - b, p, _constrain(init_params(m, key), psh))))

    def run(self, key, batch_fn, steps: int = 3) -> dict:
        """Losses of ``steps`` steps, per-leaf norms of the first clipped
        gradient and of the parameters' change over all of them."""
        params = self._init(key)
        mu, nu = self._zeros(params), self._zeros(params)
        losses, grad_norms = [], None
        for i in range(steps):
            tokens, labels = batch_fn(i)
            val, g = self._grad(params, tokens, labels)
            losses.append(float(val))
            if i == 0:
                grad_norms = np.asarray(self._norms(g), np.float64)
            params, mu, nu = self._update(jnp.int32(i),
                                          jnp.float32(lr_at(self.opt, i)),
                                          params, g, mu, nu)
            del g
        del mu, nu
        change = np.asarray(self._change(params, key), np.float64)
        del params
        return {"losses": losses, "grad_norms": grad_norms.tolist(),
                "change_norms": change.tolist()}

