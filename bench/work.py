"""Operations and least bytes of the served model's work, computed from
the configuration's sizes alone, whatever implements them.

FLOPs count a multiply-add as 2.  Only useful work counts: prompt
tokens actually admitted (not the padding of a length bucket or of an
admission batch), causal attention over the positions each token sees,
and the output projection where logits are needed.  Bytes are the least
a step must move through HBM: each weight once, and each active slot's
live KV once.
"""
from __future__ import annotations

INT4_BYTES = 0.5
SCALE_BYTES = 4          # f32 group scale and zero point, f32 act_scale
ACT_BYTES = 2            # bf16 activations, KV, embedding and lm_head


def _linears(m: dict):
    """(n_in, n_out) of each block linear of one layer."""
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return [(d, q), (d, kv), (d, kv), (q, d),
            (d, m["d_ff"]), (d, m["d_ff"]), (m["d_ff"], d)]


def block_params(m: dict) -> int:
    """Weights of the block linears over all layers."""
    return m["n_layers"] * sum(a * b for a, b in _linears(m))


def padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 256) * 256


def attn_flops(m: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs, all layers."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * pairs


def prefill_flops(m: dict, n: int, start: int = 0) -> float:
    """Prefill of ``n`` prompt tokens at positions ``start..start+n-1``
    (causal), with logits at the last position only."""
    pairs = n * start + n * (n + 1) / 2
    return (2.0 * block_params(m) * n + attn_flops(m, pairs)
            + 2.0 * m["d_model"] * m["vocab_size"])


def decode_flops(m: dict, contexts) -> float:
    """One decode step over active slots whose caches hold ``contexts``
    entries before the step (each new token sees context + 1 keys)."""
    contexts = list(contexts)
    per_token = 2.0 * block_params(m) + 2.0 * m["d_model"] * m["vocab_size"]
    return len(contexts) * per_token + attn_flops(
        m, sum(c + 1 for c in contexts))


def weight_bytes(m: dict, group: int = 64) -> float:
    """Packed int4 block linears (codes, group scales and zeros,
    act_scale), bf16 lm_head and norms."""
    lin = 0.0
    for n_in, n_out in _linears(m):
        lin += n_in * n_out * INT4_BYTES
        lin += 2 * (n_in // group) * n_out * SCALE_BYTES
        lin += n_in * SCALE_BYTES
    norms = (2 * m["n_layers"] + 1) * m["d_model"] * ACT_BYTES
    head = m["d_model"] * padded_vocab(m) * ACT_BYTES
    return m["n_layers"] * lin + norms + head


def kv_bytes_per_token(m: dict) -> float:
    return 2.0 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * ACT_BYTES


def decode_bytes(m: dict, contexts) -> float:
    """Least HBM bytes of one decode step: every weight once, each active
    slot's live KV read once and its new entry written, the embedding
    rows it gathers."""
    contexts = list(contexts)
    kv = kv_bytes_per_token(m) * sum(c + 1 for c in contexts)
    emb = len(contexts) * m["d_model"] * ACT_BYTES
    return weight_bytes(m) + kv + emb
