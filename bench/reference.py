"""The benchmark's own plain float32 reference of the served model.

A llama-style decoder (RMSNorm, rotary embedding on interleaved pairs,
grouped-query causal attention, SwiGLU), written in straight
``jax.numpy`` at the highest matmul precision: no Pallas kernel, no KV
cache, no paging, no batching.  It imports nothing of the program under
test.

Its weights are its own: :class:`FpModel` draws the configuration's
floating-point weights as the launcher's initialization defines them
(``PRNGKey(0)``, normal ``1/sqrt(n_in)``, embedding ``0.02``, norms at
one, stored in the configuration's dtype), one layer at a time.  Two
comparisons follow from them:

* :func:`table_error` holds each served int4 linear (its codes, group
  scales and zeros and FAQ smoothing scale, as the program packed them)
  against the fp weight it stands for: the squared output error on the
  fp model's own activations, as a share of the error of the plain
  round-to-nearest int4 (group 64) of the same weight.
* :func:`served_rows_logits` runs the served model's arithmetic, the
  served tables dequantized here, ``(codes - zero) * scale / act_scale``,
  with this module's own embedding, norms and head, so that served
  tokens can be held against the logits they came from.

The controls are this reference in lower precision: ``lowp=True`` rounds
every matmul operand to float8 (e4m3), the step below the bfloat16 the
configuration states for activations; ``rtn(w, 3)`` is the step below
its int4 weights.

Departures from the published architectures are those of the program's
block, listed in each configuration file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512          # query rows per attention block (bounds the scores)
LEN_STEP = 1024        # sequences are zero-padded to a multiple of this,
ROW_STEP = 512         # and the rows read to one of this: few compiles


def _round(x, lowp):
    return x.astype(jnp.float8_e4m3fn).astype(F32) if lowp else x


def _mm(a, b, lowp):
    return _round(a, lowp) @ _round(b, lowp)


def dequant(w) -> jax.Array:
    """Dense f32 ``(n_in, n_out)`` weight of one layer's linear.  ``w`` is
    a plain array or a dict of the packed format's arrays: ``codes``
    uint8 ``(n_in / 2, n_out)`` holding row ``2i`` in the low nibble and
    row ``2i + 1`` in the high one, ``scale`` and ``zero`` f32
    ``(n_groups, n_out)`` over groups of consecutive input rows, and an
    optional per-input-row ``act_scale``."""
    if not isinstance(w, dict):
        return w.astype(F32)
    c = w["codes"]
    lo = (c & 0x0F).astype(F32)
    hi = (c >> 4).astype(F32)
    codes = jnp.stack([lo, hi], axis=1).reshape(2 * c.shape[0], c.shape[1])
    g = codes.shape[0] // w["scale"].shape[0]
    zero = jnp.repeat(w["zero"].astype(F32), g, axis=0)
    scale = jnp.repeat(w["scale"].astype(F32), g, axis=0)
    dense = (codes - zero) * scale
    if w.get("act_scale") is not None:
        dense = dense / w["act_scale"].astype(F32)[:, None]
    return dense


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x: (T, heads, hd); rotates pairs (2i, 2i+1) by pos * theta^(-2i/hd)."""
    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, lowp):
    """Causal attention in blocks of query rows.  q: (T, H, hd);
    k, v: (T, H, hd) already repeated to the query heads."""
    t, _, hd = q.shape
    outs = []
    for start in range(0, t, Q_BLOCK):
        qb = q[start:start + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", _round(qb, lowp),
                       _round(k, lowp)) * hd ** -0.5
        rows = start + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where((jnp.arange(t)[None, :] <= rows)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", _round(p, lowp),
                               _round(v, lowp)))
    return jnp.concatenate(outs, axis=0)


def _block(dims, lowp, x, p):
    n_heads, n_kv, hd, theta, eps = dims
    t = x.shape[0]
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rope(_mm(h, dequant(p["wq"]), lowp).reshape(t, n_heads, hd), theta)
    k = _rope(_mm(h, dequant(p["wk"]), lowp).reshape(t, n_kv, hd), theta)
    v = _mm(h, dequant(p["wv"]), lowp).reshape(t, n_kv, hd)
    group = n_heads // n_kv                 # query head i reads kv head i // group
    o = _attention(q, jnp.repeat(k, group, axis=1),
                   jnp.repeat(v, group, axis=1), lowp)
    x = x + _mm(o.reshape(t, n_heads * hd), dequant(p["wo"]), lowp)
    h = _rms_norm(x, p["mlp_norm"], eps)
    ff = jax.nn.silu(_mm(h, dequant(p["w_gate"]), lowp)) \
        * _mm(h, dequant(p["w_up"]), lowp)
    return x + _mm(ff, dequant(p["w_down"]), lowp)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _rows_logits(dims, lowp, params, tokens, rows):
    """Logits ``(len(rows), vocab)`` at positions ``rows`` of the causal
    forward over ``tokens``; layers run in a scan, so one layer's
    dequantized weights are live at a time."""
    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda x, p: (_block(dims[:5], lowp, x, p), None),
                        x, params["blocks"])
    x = _rms_norm(x[rows], params["final_norm"], dims[4])
    logits = _mm(x, params["lm_head"].astype(F32), lowp)
    return logits[:, :dims[5]]


def served_rows_logits(model, params, prompt, served, *, lowp=False):
    """Reference logits at the positions that predicted each served token:
    row ``j`` is the distribution after ``prompt`` and ``served[:j]``.
    ``model`` holds ``n_heads``, ``n_kv_heads``, ``head_dim``,
    ``rope_theta``, ``norm_eps`` and ``vocab_size``."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    n = len(seq)
    padded = np.zeros(-(-n // LEN_STEP) * LEN_STEP, np.int32)
    padded[:n] = seq
    n_out = len(served)
    rows = np.zeros(-(-n_out // ROW_STEP) * ROW_STEP, np.int32)
    rows[:n_out] = len(prompt) - 1 + np.arange(n_out)
    dims = (model["n_heads"], model["n_kv_heads"], model["head_dim"],
            float(model["rope_theta"]), float(model["norm_eps"]),
            model["vocab_size"])
    with jax.default_matmul_precision("highest"):
        out = _rows_logits(dims, lowp, params, jnp.asarray(padded),
                           jnp.asarray(rows))
    return np.asarray(out[:n_out])


LINEARS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
GROUP = 64


def _shapes(model) -> dict:
    d, hd, ff = model["d_model"], model["head_dim"], model["d_ff"]
    return {"wq": (d, model["n_heads"] * hd),
            "wk": (d, model["n_kv_heads"] * hd),
            "wv": (d, model["n_kv_heads"] * hd),
            "wo": (model["n_heads"] * hd, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal(key, n_in, n_out, scale, dtype):
    return (jax.random.normal(key, (n_in, n_out)) * scale).astype(dtype)


class FpModel:
    """The configuration's floating-point weights, drawn from ``PRNGKey(0)``
    in the launcher's order: keys for embedding, blocks and head; one key
    per layer; seven per layer, one for each linear in ``LINEARS``."""

    def __init__(self, model):
        self.model = model
        self.dtype = jnp.dtype(model.get("dtype", "float32"))
        self.v_pad = -(-model["vocab_size"] // 256) * 256
        k_emb, k_blocks, self.k_head = jax.random.split(
            jax.random.PRNGKey(0), 3)
        self.k_emb = k_emb
        self.layer_keys = jax.random.split(k_blocks, model["n_layers"])
        self.shapes = _shapes(model)

    def layer(self, l: int) -> dict:
        ks = jax.random.split(self.layer_keys[l], len(LINEARS))
        return {n: _normal(k, *self.shapes[n], self.shapes[n][0] ** -0.5,
                           self.dtype)
                for n, k in zip(LINEARS, ks)}

    def embed(self) -> jax.Array:
        return _normal(self.k_emb, self.v_pad, self.model["d_model"], 0.02,
                       self.dtype)

    def logit_params(self, tables: dict) -> dict:
        """The served model's tree: ``tables`` (the served linears, each
        stacked over layers) with this model's embedding, norms and head."""
        d, L = self.model["d_model"], self.model["n_layers"]
        ones = jnp.ones((L, d), self.dtype)
        blocks = dict(tables, attn_norm=ones, mlp_norm=ones)
        return {"embed": self.embed(),
                "blocks": blocks,
                "final_norm": jnp.ones((d,), self.dtype),
                "lm_head": _normal(self.k_head, d, self.v_pad, d ** -0.5,
                                   self.dtype)}


def rtn(w, bits: int) -> jax.Array:
    """Plain asymmetric round-to-nearest of an ``(n_in, n_out)`` weight in
    groups of ``GROUP`` input rows, each group's range widened to hold
    zero; returned dequantized, f32."""
    n_in, n_out = w.shape
    wg = w.astype(F32).reshape(n_in // GROUP, GROUP, n_out)
    lo = jnp.minimum(wg.min(axis=1, keepdims=True), 0.0)
    hi = jnp.maximum(wg.max(axis=1, keepdims=True), 0.0)
    top = 2 ** bits - 1
    scale = jnp.maximum((hi - lo) / top, 1e-8)
    zero = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(wg / scale) + zero, 0, top)
    return ((q - zero) * scale).reshape(n_in, n_out)


@functools.partial(jax.jit, static_argnums=(0,))
def _linear_inputs(dims, x, p):
    """One fp block over ``x``: the next residual stream and the input of
    each linear."""
    n_heads, n_kv, hd, theta, eps = dims
    t = x.shape[0]
    h = _rms_norm(x, jnp.ones((x.shape[1],), F32), eps)
    q = _rope((h @ p["wq"].astype(F32)).reshape(t, n_heads, hd), theta)
    k = _rope((h @ p["wk"].astype(F32)).reshape(t, n_kv, hd), theta)
    v = (h @ p["wv"].astype(F32)).reshape(t, n_kv, hd)
    group = n_heads // n_kv
    o = _attention(q, jnp.repeat(k, group, axis=1),
                   jnp.repeat(v, group, axis=1), False)
    o = o.reshape(t, n_heads * hd)
    x = x + o @ p["wo"].astype(F32)
    h2 = _rms_norm(x, jnp.ones((x.shape[1],), F32), eps)
    ff = jax.nn.silu(h2 @ p["w_gate"].astype(F32)) \
        * (h2 @ p["w_up"].astype(F32))
    x = x + ff @ p["w_down"].astype(F32)
    ins = {"wq": h, "wk": h, "wv": h, "wo": o, "w_gate": h2, "w_up": h2,
           "w_down": ff}
    return x, ins


@functools.partial(jax.jit, static_argnums=(3,))
def _out_err(x, w, served, low_bits):
    """Squared output error on ``x`` of the served linear (or, with
    ``low_bits``, of the plain RTN at that width in its place) and of the
    plain int4 RTN, both against the fp weight."""
    w32 = w.astype(F32)
    got = rtn(w, low_bits) if low_bits else dequant(served)
    err = x @ (got - w32)
    base = x @ (rtn(w, 4) - w32)
    return jnp.mean(err * err), jnp.mean(base * base)


def table_error(fp: FpModel, tables: dict, tokens, low_bits: int = 0):
    """For each layer and linear, the served table's squared output error
    on the fp model's activations over ``tokens``, as a share of plain
    int4 RTN's.  ``low_bits`` puts RTN at that width in the served
    tables' place (the control).  Returns ``{"<layer>.<linear>": share}``."""
    m = fp.model
    dims = (m["n_heads"], m["n_kv_heads"], m["head_dim"],
            float(m["rope_theta"]), float(m["norm_eps"]))
    emb = fp.embed()
    out = {}
    with jax.default_matmul_precision("highest"):
        x = emb[jnp.asarray(np.asarray(tokens))].astype(F32)
        del emb
        for l in range(m["n_layers"]):
            p = fp.layer(l)
            x_next, ins = _linear_inputs(dims, x, p)
            for n in LINEARS:
                served = jax.tree_util.tree_map(lambda a: a[l], tables[n])
                e, base = _out_err(ins[n], p[n], served, low_bits)
                out[f"{l}.{n}"] = float(e) / max(float(base), 1e-30)
            x = x_next
    return out


def logit_gaps(ref_rows: np.ndarray, picked) -> np.ndarray:
    """How far each picked token's reference logit lies below the best
    reference logit at its position (0 where it is the argmax)."""
    picked = np.asarray(picked)
    return ref_rows.max(axis=-1) - ref_rows[np.arange(len(picked)), picked]
