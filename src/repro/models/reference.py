"""Plain float32 reference of the dense decoder family (internlm2, qwen3).

The forward pass written straight from the architecture's description in
``jax.numpy``: RMSNorm, rotary embeddings (half-split rotation), grouped-
query causal attention with optional per-head qk-norm and QKV bias, a
SwiGLU feed-forward, a final RMSNorm and the output head.  No kernels,
cache, batching, sharding or rematerialisation, and no code shared with
``models.transformer``: only the parameter tree is common, so the same
weights can be fed to both.

Weights are upcast to float32 (exact from bfloat16) inside each layer's
step of a ``lax.scan``, so the float32 copy of the weights never exists
whole on the device, and every contraction runs at HIGHEST precision (a
TPU otherwise multiplies float32 in reduced precision).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

_HI = lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _rmsnorm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gamma)


def _rope(x, pos, theta):
    """x (S, H, dh); rotate the (first half, second half) pairs."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg: ModelConfig, x, lp):
    s = x.shape[0]
    pos = jnp.arange(s)
    a = lp["attn"]
    h = _rmsnorm(x, lp["norm1"], cfg.norm_eps)
    q = jnp.einsum("sd,dhk->shk", h, _f32(a["wq"]), precision=_HI)
    k = jnp.einsum("sd,dhk->shk", h, _f32(a["wk"]), precision=_HI)
    v = jnp.einsum("sd,dhk->shk", h, _f32(a["wv"]), precision=_HI)
    if "bq" in a:
        q, k, v = q + _f32(a["bq"]), k + _f32(a["bk"]), v + _f32(a["bv"])
    if cfg.qk_norm:
        q = _rmsnorm(q, a["q_norm"], cfg.norm_eps)
        k = _rmsnorm(k, a["k_norm"], cfg.norm_eps)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    group = cfg.n_heads // cfg.n_kv_heads       # query head j reads kv j//group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("shk,thk->hst", q, k, precision=_HI) \
        / jnp.sqrt(jnp.float32(cfg.head_dim))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hst,thk->shk", probs, v, precision=_HI)
    x = x + jnp.einsum("shk,hkd->sd", o, _f32(a["wo"]), precision=_HI)
    f = lp["ffn"]
    h = _rmsnorm(x, lp["norm2"], cfg.norm_eps)
    gate = jax.nn.silu(jnp.dot(h, _f32(f["w_gate"]), precision=_HI))
    up = jnp.dot(h, _f32(f["w_up"]), precision=_HI)
    return x + jnp.dot(gate * up, _f32(f["w_down"]), precision=_HI), None


@functools.partial(jax.jit, static_argnums=(1,))
def reference_logits(params, cfg: ModelConfig, tokens):
    """Float32 logits ``(S, vocab)`` of one sequence ``tokens`` (S,)."""
    if cfg.family != "dense":
        raise ValueError(f"reference covers the dense family, not "
                         f"{cfg.family!r}")
    x = _f32(params["embed"][tokens])
    x, _ = lax.scan(functools.partial(_layer, cfg), x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.dot(x, _f32(head), precision=_HI)
