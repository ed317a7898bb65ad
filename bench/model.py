"""The benchmark's own dense decoder: weights from the seed and the plain
float32 reference (forward, loss, AdamW).

It imports nothing of the program.  Its weights are laid out as the
program's parameter tree is (``embed``, ``layers`` stacked on a leading
layer axis, ``final_norm``, ``lm_head``), because that tree is how the
system under test takes weights; the harness checks the two trees agree
before a run.  The forward pass follows the configuration file:
RMSNorm, rotary embeddings (half-split rotation), grouped-query causal
attention with optional per-head qk-norm, SwiGLU, final RMSNorm, untied
or tied output head.  Every contraction runs at HIGHEST precision (a TPU
otherwise multiplies float32 in reduced precision).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST


class Sizes:
    """The sizes a configuration file states, under short names."""

    def __init__(self, c: dict):
        self.d = c["hidden_size"]
        self.h = c["num_attention_heads"]
        self.kv = c["num_key_value_heads"]
        self.dh = c.get("head_dim") or self.d // self.h
        self.ff = c["intermediate_size"]
        self.vocab = c["vocab_size"]
        self.layers = c["num_hidden_layers"]
        self.eps = c["rms_norm_eps"]
        self.theta = float(c["rope_theta"])
        self.qk_norm = bool(c.get("qk_norm", False))
        self.tied = bool(c["tie_word_embeddings"])
        self.dtype = jnp.dtype(c["torch_dtype"])

    def __hash__(self):
        return hash(tuple(sorted(vars(self).items(), key=str)))

    def __eq__(self, other):
        return vars(self) == vars(other)


def key_of(seed: int):
    """A PRNG key from any whole-number seed (all of its bits count)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def shapes(s: Sizes) -> dict:
    L, d, h, kv, dh, ff = s.layers, s.d, s.h, s.kv, s.dh, s.ff
    attn = {"wq": (L, d, h, dh), "wk": (L, d, kv, dh), "wv": (L, d, kv, dh),
            "wo": (L, h, dh, d)}
    if s.qk_norm:
        attn.update(q_norm=(L, dh), k_norm=(L, dh))
    tree = {"embed": (s.vocab, d), "final_norm": (d,),
            "layers": {"attn": attn, "norm1": (L, d), "norm2": (L, d),
                       "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                               "w_down": (L, ff, d)}}}
    if not s.tied:
        tree["lm_head"] = (d, s.vocab)
    return tree


def init(s: Sizes, key) -> dict:
    """Random weights in the configuration's dtype: gains 1, embedding
    N(0, 0.02^2), each matrix a normal truncated at two deviations with
    deviation ``n ** -0.5``, ``n`` the size of its first axis after the
    layer axis (the scale at which the bfloat16 model stays close to the
    float32 reference through every layer; with ``1 / fan_in`` on the
    output projection its logits drift from it by a tenth of their RMS
    at two layers).  Call under ``jax.jit``."""
    tree = shapes(s)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, shape), k in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            out.append(jnp.ones(shape, s.dtype))
            continue
        if "embed" in name:
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            n = shape[1] if "layers" in name else shape[0]
            w = n ** -0.5 * jax.random.truncated_normal(k, -2.0, 2.0, shape)
        out.append(w.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# -- the plain forward pass ------------------------------------------------

def _f32(x):
    return x.astype(jnp.float32)


def _rmsnorm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gamma)


def _rope(x, theta):
    """x (S, H, dh) at positions 0..S-1; rotate (first half, second half)."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class Low(NamedTuple):
    """A lower precision for the control: ``weight`` rounds every weight
    matrix before use, ``act`` (if given) every other operand of a
    contraction (activations, q, k, v, attention probabilities)."""
    weight: Callable
    act: Callable | None = None


def _mm(spec, a, b, low=None):
    if low is not None:
        b = low.weight(b)
        a = _act(a, low)
    return jnp.einsum(spec, a, _f32(b), precision=_HI)


def _act(x, low):
    return x if low is None or low.act is None else low.act(x)


def _layer(s: Sizes, low, x, lp):
    a = lp["attn"]
    h = _rmsnorm(x, lp["norm1"], s.eps)
    q = _mm("sd,dhk->shk", h, a["wq"], low)
    k = _mm("sd,dhk->shk", h, a["wk"], low)
    v = _mm("sd,dhk->shk", h, a["wv"], low)
    if s.qk_norm:
        q, k = _rmsnorm(q, a["q_norm"], s.eps), _rmsnorm(k, a["k_norm"], s.eps)
    q, k = _rope(q, s.theta), _rope(k, s.theta)
    group = s.h // s.kv                    # query head j reads kv j // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    n = x.shape[0]
    scores = jnp.einsum("shk,thk->hst", _act(q, low), _act(k, low),
                        precision=_HI) / np.sqrt(s.dh)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hst,thk->shk", _act(probs, low), _act(v, low),
                   precision=_HI)
    x = x + _mm("shk,hkd->sd", o, a["wo"], low)
    f = lp["ffn"]
    h = _rmsnorm(x, lp["norm2"], s.eps)
    g = jax.nn.silu(_mm("sd,df->sf", h, f["w_gate"], low))
    u = _mm("sd,df->sf", h, f["w_up"], low)
    return x + _mm("sf,fd->sd", g * u, f["w_down"], low), None


def _hidden(s: Sizes, params, tokens, low=None, remat=False):
    x = _f32(params["embed"][tokens])
    body = functools.partial(_layer, s, low)
    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, params["layers"])
    return _rmsnorm(x, params["final_norm"], s.eps)


def _head(s: Sizes, params):
    return params["embed"].T if s.tied else params["lm_head"]


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(s: Sizes, params, tokens, low=None):
    """Float32 logits (S, vocab) of one sequence ``tokens`` (S,); with
    ``low`` (a ``Low``), the lower-precision control's."""
    x = _hidden(s, params, tokens, low)
    return _mm("sd,dv->sv", x, _head(s, params), low)


def loss(s: Sizes, params, batch, low=None) -> jax.Array:
    """Mean next-token cross-entropy over a (B, S) batch, float32."""
    def one(tokens, targets):
        x = _hidden(s, params, tokens, low, remat=True)
        lg = _mm("sd,dv->sv", x, _head(s, params), low)
        lz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.mean(lz - gold)
    return jnp.mean(jax.vmap(one)(batch["tokens"], batch["targets"]))


def _fp8(x, axes):
    x = _f32(x)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = lax.optimization_barrier((x / scale).astype(jnp.float8_e4m3fn))
    return q.astype(jnp.float32) * scale


def fp8_weights(w):
    """Weight rounded to float8 e4m3 with one scale per output column
    (amax / 448), back in float32: the precision below bfloat16."""
    return _fp8(w, tuple(range(w.ndim - 1)))


def fp8_rows(x):
    """Operand rounded to float8 e4m3 with one scale per row (its last
    axis)."""
    return _fp8(x, (-1,))


#: The control: every contraction computed on fp8 operands,
#: weights, activations, q, k, v and attention probabilities alike.
FP8 = Low(fp8_weights, fp8_rows)


# -- AdamW as the configuration states it ---------------------------------

def lr_at(opt: dict, step) -> jax.Array:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay
    to ``min_lr_ratio * lr`` at ``total_steps`` (``step`` counts from 1)."""
    t = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(t / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1),
                    0.0, 1.0)
    decay = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * frac))
    return opt["lr"] * warm * decay


def adamw(opt: dict, params, grads, m, v, step: int, dtype):
    """One AdamW step with global-norm clipping; parameters are stored in
    ``dtype`` (the configuration's), everything else in float32.
    Returns (params, m, v, clipped grads)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gn + 1e-12))
    b1, b2 = opt["beta1"], opt["beta2"]
    lr = lr_at(opt, step)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def one(p, g, m_, v_):
        g = g * scale
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        p32 = _f32(p)
        upd = -lr * ((m2 / bc1) / (jnp.sqrt(v2 / bc2) + opt["eps"])
                     + opt["weight_decay"] * p32)
        return (p32 + upd).astype(dtype), m2, v2, g

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2), pick(3)
