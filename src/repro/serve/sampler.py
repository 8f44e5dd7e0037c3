"""On-device batched token sampling for the serve loop.

One jitted call samples the whole decode batch: greedy, temperature,
top-k, and top-p (nucleus) are all expressed per-slot, so mixed-policy
batches share a single XLA program and the decode loop transfers one
int32 per slot per step instead of a vocab-size logits row.

The speculative-decoding accept/resample step (:func:`spec_accept`)
lives here too: it consumes the draft's proposal distributions and the
target's verify logits and applies standard leftover-probability
rejection sampling (Leviathan et al.), so the emitted stream is an
exact sample from the target policy — and greedy output is
token-for-token identical to non-speculative decode.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _apply_top_k(logits: jax.Array, top_k: jax.Array) -> jax.Array:
    """Mask all but each row's k highest logits (k=0 disables)."""
    v = logits.shape[-1]
    desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        desc, jnp.clip(top_k.astype(jnp.int32) - 1, 0, v - 1)[:, None],
        axis=-1)
    use_topk = (top_k > 0)[:, None]
    return jnp.where(use_topk & (logits < kth), -jnp.inf, logits)


def _apply_top_p(scaled: jax.Array, top_p: jax.Array) -> jax.Array:
    """Nucleus mask on already temperature-scaled logits.

    Keeps, per row, the smallest set of highest-probability tokens whose
    cumulative probability reaches ``top_p`` (the top-1 token always
    survives).  ``top_p <= 0`` or ``>= 1`` disables the mask for that
    row.
    """
    probs = jax.nn.softmax(scaled, axis=-1)
    order = jnp.argsort(-probs, axis=-1)
    sorted_p = jnp.take_along_axis(probs, order, axis=-1)
    csum = jnp.cumsum(sorted_p, axis=-1)
    # token i (sorted) stays while the mass *before* it is < top_p
    keep_sorted = (csum - sorted_p) < top_p[:, None]
    inv = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    active = ((top_p > 0.0) & (top_p < 1.0))[:, None]
    return jnp.where(active & ~keep, -jnp.inf, scaled)


def policy_in_use(top_k, top_p) -> Tuple[bool, bool]:
    """Host-side "does any row actually use top-k / top-p" predicates.

    The single source of truth for the disable semantics (``top_k <= 0``,
    ``top_p <= 0`` or ``>= 1``): both the engine's jitted decode bodies
    and the speculative cycle specialize their compiled programs on
    these flags, and they must agree or the draft policy would diverge
    from the target policy.
    """
    import numpy as np
    tk, tp = np.asarray(top_k), np.asarray(top_p)
    return bool((tk > 0).any()), bool(((tp > 0) & (tp < 1)).any())


@jax.named_scope("sample")
def sample_tokens(logits: jax.Array, temperature: jax.Array,
                  top_k: Optional[jax.Array], key: jax.Array,
                  top_p: Optional[jax.Array] = None) -> jax.Array:
    """Sample one token per batch row.

    logits: (B, V) — may carry the -1e30 padded-vocab mask from
    :func:`~repro.models.common.logits_from_hidden`; masked columns have
    probability zero and are never the argmax.
    temperature: (B,) f32 — ``<= 0`` means greedy for that row.
    top_k: (B,) int32 — ``0`` disables top-k for that row; otherwise only
    the k highest logits stay eligible.
    key: PRNG key for the whole batch (rows draw independent noise).
    top_p: optional (B,) f32 nucleus threshold — ``<= 0`` or ``>= 1``
    disables it for that row; applied after top-k on the
    temperature-scaled distribution.

    ``top_k``/``top_p`` may be ``None`` when the caller knows no row
    uses them: the full-vocab sort/argsort behind the masks is the
    expensive part of this function, and the serve engine specializes
    it away per batch (the decode loop runs this every token).

    Returns (B,) int32.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = logits if top_k is None else _apply_top_k(logits, top_k)

    do_sample = temperature > 0
    scaled = masked / jnp.maximum(temperature, 1e-6)[:, None]
    if top_p is not None:
        scaled = _apply_top_p(scaled, top_p)
    # greedy rows skip the (potentially inf-scaled) division result
    scaled = jnp.where(do_sample[:, None], scaled, 0.0)
    drawn = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(do_sample, drawn, greedy)


def policy_probs(logits: jax.Array, temperature: jax.Array,
                 top_k: Optional[jax.Array] = None,
                 top_p: Optional[jax.Array] = None) -> jax.Array:
    """The per-row sampling policy as an explicit distribution.

    Returns (B, V) probabilities: softmax of the temperature-scaled,
    top-k/top-p-masked logits for sampling rows, and an exact one-hot at
    the argmax for greedy rows (``temperature <= 0``).  This is the
    distribution :func:`sample_tokens` draws from, materialized so the
    speculative accept/resample rule can evaluate p(x)/q(x) ratios.

    ``top_k``/``top_p`` may be ``None`` when the caller knows no row in
    the batch uses them — the full-vocab sort/argsort those masks cost
    is the expensive part of this function, so the speculative cycle
    specializes it away per batch.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    onehot = (jnp.arange(v)[None, :]
              == jnp.argmax(logits, axis=-1)[:, None]).astype(jnp.float32)
    masked = logits if top_k is None else _apply_top_k(logits, top_k)
    scaled = masked / jnp.maximum(temperature, 1e-6)[:, None]
    if top_p is not None:
        scaled = _apply_top_p(scaled, top_p)
    probs = jax.nn.softmax(scaled, axis=-1)
    return jnp.where((temperature > 0)[:, None], probs, onehot)


def draw_from_probs(probs: jax.Array, key: jax.Array) -> jax.Array:
    """Categorical draw from explicit probabilities (last axis).

    Zero-probability entries are exactly excluded (``log 0 = -inf``); a
    one-hot row draws its hot index deterministically, so greedy rows
    fed through :func:`policy_probs` stay deterministic.
    """
    return jax.random.categorical(key, jnp.log(probs), axis=-1) \
              .astype(jnp.int32)


def spec_accept(draft_tokens: jax.Array, draft_probs: jax.Array,
                target_logits: jax.Array, temperature: jax.Array,
                top_k: Optional[jax.Array], top_p: Optional[jax.Array],
                key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Leftover-probability rejection sampling over one speculative burst.

    draft_tokens: (B, K) int32 — draft proposals d_1..d_K.
    draft_probs: (B, K, V) — the draft *policy* distribution each
    proposal was drawn from (same temperature/top-k/top-p policy).
    target_logits: (B, K+1, V) — verify logits; position ``i`` is the
    target's next-token distribution after consuming the last committed
    token plus d_1..d_i.
    temperature/top_k/top_p: (B,) per-slot policy (shared with the draft).

    Returns ``(out_tokens (B, K+1), n_accept (B,))``: proposal ``d_{i+1}``
    is accepted with probability ``min(1, p_i(d)/q_i(d))``; the first
    rejected position resamples from ``norm(max(p - q, 0))``; if all K
    are accepted a bonus token is drawn from the target's last position.
    The emitted burst is ``out_tokens[:, :n_accept + 1]``.  Greedy rows
    (one-hot p and q) reduce to "accept while the draft token equals the
    target argmax, then emit the target argmax" — token-for-token
    identical to non-speculative greedy decode.
    """
    b, k = draft_tokens.shape
    v = target_logits.shape[-1]
    p = jax.vmap(policy_probs, in_axes=(1, None, None, None), out_axes=1)(
        target_logits.astype(jnp.float32), temperature, top_k, top_p)

    px = jnp.take_along_axis(p[:, :k], draft_tokens[..., None],
                             axis=-1)[..., 0]              # (B, K)
    qx = jnp.take_along_axis(draft_probs, draft_tokens[..., None],
                             axis=-1)[..., 0]              # (B, K)
    k_u, k_r, k_b = jax.random.split(key, 3)
    u = jax.random.uniform(k_u, (b, k))
    # accept iff u < p/q  <=>  u*q < p (q(x) > 0 since x ~ q); greedy
    # rows have q one-hot so this is exactly "draft == target argmax"
    accept = (u * qx) < px
    n_accept = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)

    # leftover distribution per position; if p == q exactly the residual
    # is empty — that position is only ever read when rejected (p != q
    # at the drawn token), but guard the normalization anyway
    res = jnp.clip(p[:, :k] - draft_probs, 0.0, None)
    norm = res.sum(axis=-1, keepdims=True)
    res = jnp.where(norm > 0, res / jnp.maximum(norm, 1e-30), p[:, :k])
    resampled = draw_from_probs(res, k_r)                  # (B, K)
    bonus = draw_from_probs(p[:, k], k_b)                  # (B,)

    corrections = jnp.concatenate([resampled, bonus[:, None]], axis=1)
    padded = jnp.concatenate(
        [draft_tokens, jnp.zeros((b, 1), jnp.int32)], axis=1)
    idx = jnp.arange(k + 1)[None, :]
    out = jnp.where(idx < n_accept[:, None], padded, corrections)
    return out.astype(jnp.int32), n_accept.astype(jnp.int32)
