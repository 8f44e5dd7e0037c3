"""Plain float32 reference of the dense llama-style decoder (:class:`DenseLM`).

Straight ``jax.numpy`` at ``float32`` with the highest matmul precision:
no Pallas kernel, no KV cache, no paging, no batching, no bucketing.
Packed weights are dequantized to the exact original-domain weight the
serving dequant-matmul realizes (``deq(codes) / act_scale``), so the
reference and the engine compute the same model; only the arithmetic
differs.  The serving path is checked against it on logits
(:func:`greedy_gaps`), since with random weights the largest logit
changes under rounding and tokens alone would flake.

Departures from the published architecture: none beyond those of
:class:`DenseLM` itself (untied embedding, RoPE on interleaved pairs).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizer import QuantizedTensor, dequantize_groupwise

F32 = jnp.float32


def _weight(w) -> jax.Array:
    """Dense f32 ``(n_in, n_out)`` weight of a plain or packed leaf."""
    if isinstance(w, QuantizedTensor):
        dense = dequantize_groupwise(dataclasses.replace(w, act_scale=None))
        if w.act_scale is not None:
            dense = dense / w.act_scale.astype(F32)[:, None]
        return dense
    return w.astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x: (T, heads, hd); rotates the pairs (2i, 2i+1) by pos * theta^(-2i/hd)."""
    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv               # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _block(cfg, x, p):
    t = x.shape[0]
    hd = cfg.head_dim_
    h = _rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q = _rope((h @ _weight(p["wq"])).reshape(t, cfg.n_heads, hd),
              cfg.rope_theta)
    k = _rope((h @ _weight(p["wk"])).reshape(t, cfg.n_kv_heads, hd),
              cfg.rope_theta)
    v = (h @ _weight(p["wv"])).reshape(t, cfg.n_kv_heads, hd)
    # query head i reads KV head i // (n_heads / n_kv_heads)
    group = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + o.reshape(t, cfg.n_heads * hd) @ _weight(p["wo"])
    h = _rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    ff = jax.nn.silu(h @ _weight(p["w_gate"])) * (h @ _weight(p["w_up"]))
    return x + ff @ _weight(p["w_down"])


@functools.partial(jax.jit, static_argnums=0)
def _logits(cfg, params, tokens):
    x = params["embed"][tokens].astype(F32)
    # scan: one layer's dequantized weights are live at a time
    x, _ = jax.lax.scan(lambda x, p: (_block(cfg, x, p), None), x,
                        params["blocks"])
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x @ _weight(params["lm_head"]))[:, :cfg.vocab_size]


def reference_logits(cfg, params, tokens) -> jax.Array:
    """``(T, vocab)`` f32 logits of the causal forward over ``tokens`` (T,),
    left on the device."""
    with jax.default_matmul_precision("highest"):
        return _logits(cfg, params, jnp.asarray(tokens, jnp.int32))


def greedy_gaps(ref_logits, prompt_len: int, emitted) -> np.ndarray:
    """For each greedily emitted token, how far its reference logit lies
    below the largest reference logit at that position (0 where the
    engine picked the reference's argmax).  ``ref_logits`` covers the
    prompt followed by ``emitted``; token ``j`` was predicted at position
    ``prompt_len - 1 + j``."""
    emitted = np.asarray(emitted)
    rows = np.asarray(ref_logits[prompt_len - 1:prompt_len - 1 + len(emitted)])
    return rows.max(axis=-1) - rows[np.arange(len(emitted)), emitted]
