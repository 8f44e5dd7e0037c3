"""Mixture-of-Experts LM (llama4-maverick, qwen2-moe).

Dispatch is gather/scatter (sort-by-expert + capacity buffers), O(N·d),
never the O(N·E·C·d) one-hot einsum.  Two execution paths share the same
math:

* **local** — pure jnp, used on CPU (tests, calibration) and whenever no
  mesh is active.
* **sharded** — ``shard_map`` over the production mesh: tokens sharded on
  (pod, data); experts sharded on the 16-way ``model`` axis (padded to a
  multiple of it, pad experts masked in the router); expert weights
  additionally FSDP-sharded on (pod, data) along d_model and all-gathered
  per layer; token buffers exchanged with ``all_to_all`` over ``model``
  (expert parallelism).  Backward collectives come from JAX's transpose
  rules (all_gather -> psum_scatter, all_to_all -> all_to_all).

The router stays full-precision (small, sensitive); expert and shared-
expert linears are quantizable sites.  Per DESIGN.md §4, routed-expert
sites use the dispatch-weighted block-input statistic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.stats import site_stat
from repro.dist.sharding import active_mesh, row_parallel, shard_hint
from .common import (layer_scan,
                     apply_rope, chunked_attention,
                     dense_init, embed_tokens, last_valid_hidden,
                     logits_from_hidden,
                     padded_vocab, qlinear, rms_norm, stack_layer_params)
from .dense import DenseLM


def padded_experts(n_experts: int, multiple: int = 16) -> int:
    return ((n_experts + multiple - 1) // multiple) * multiple


def _capacity(n_tokens: int, k: int, n_experts: int, factor: float) -> int:
    return max(1, int(n_tokens * k * factor / n_experts + 0.999))


def _route(x_flat, router_w, n_experts_real, k):
    """Top-k routing.  Returns (probs (N,k), ids (N,k), aux_loss)."""
    logits = (x_flat @ router_w.astype(x_flat.dtype)).astype(jnp.float32)
    e_pad = router_w.shape[-1]
    pad_mask = jnp.where(jnp.arange(e_pad) < n_experts_real, 0.0, -1e30)
    logits = logits + pad_mask
    topv, topi = jax.lax.top_k(logits, k)
    probs = jax.nn.softmax(topv, axis=-1)
    # switch-style load-balance aux loss
    full_probs = jax.nn.softmax(logits, axis=-1)
    me = jnp.mean(full_probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(topi, e_pad, dtype=jnp.float32), axis=1), axis=0) / k
    aux = n_experts_real * jnp.sum(me * ce)
    return probs, topi, aux


def _dispatch(x_flat, topi, probs, e_pad, capacity):
    """Sort-by-expert capacity dispatch.

    Returns (buffers (E, C, d), dest (N*k,), keep (N*k,), src (N*k,),
    gate (N*k,)).
    """
    n, d = x_flat.shape
    k = topi.shape[-1]
    flat_e = topi.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e,
                                 num_segments=e_pad)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n * k) - offsets[sorted_e]
    keep = (rank < capacity).astype(x_flat.dtype)
    dest = sorted_e * capacity + jnp.minimum(rank, capacity - 1)
    src = order // k
    gate = probs.reshape(-1)[order].astype(x_flat.dtype)
    buf = jnp.zeros((e_pad * capacity, d), x_flat.dtype)
    buf = buf.at[dest].add(x_flat[src] * keep[:, None])
    return buf.reshape(e_pad, capacity, d), dest, keep, src, gate


def _expert_matmul(x, w):
    """(E, C, d) @ per-expert weight; FP array or QuantizedTensor."""
    from repro.core.quantizer import QuantizedTensor
    if isinstance(w, QuantizedTensor):
        from repro.kernels.ops import quant_matmul_experts
        return quant_matmul_experts(x, w).astype(x.dtype)
    return jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype))


def _expert_ffn(buf, wg, wu, wd):
    """buf (E, C, d) through per-expert SwiGLU.  Returns (out, hidden)."""
    g = _expert_matmul(buf, wg)
    u = _expert_matmul(buf, wu)
    h = jax.nn.silu(g) * u
    out = _expert_matmul(h, wd)
    return out, h


def _combine(out_buf, dest, keep, src, gate, n, d):
    contrib = out_buf.reshape(-1, d)[dest] * (keep * gate)[:, None]
    y = jnp.zeros((n, d), out_buf.dtype).at[src].add(contrib)
    return y


def moe_ffn_local(x, router_w, wg, wu, wd, cfg: ModelConfig,
                  collect: bool = False):
    """Single-device MoE FFN.  x: (B, T, d)."""
    b, t, d = x.shape
    x_flat = x.reshape(-1, d)
    n = b * t
    e_pad = router_w.shape[-1]
    k = cfg.experts_per_token
    cap = _capacity(n, k, cfg.n_experts, cfg.moe_capacity_factor)
    probs, topi, aux = _route(x_flat, router_w, cfg.n_experts, k)
    buf, dest, keep, src, gate = _dispatch(x_flat, topi, probs, e_pad, cap)
    out_buf, hidden = _expert_ffn(buf, wg, wu, wd)
    y = _combine(out_buf, dest, keep, src, gate, n, d)
    stats = {}
    if collect:
        stats["mlp_down"] = site_stat(hidden)
    return y.reshape(b, t, d), aux, stats


def _gather_expert_weight(w, axis: int, fsdp_axes):
    """FSDP all-gather of one expert weight (FP or QuantizedTensor)."""
    from repro.core.quantizer import QuantizedTensor
    if not fsdp_axes:
        return w
    if isinstance(w, QuantizedTensor):
        codes = jax.lax.all_gather(w.codes, fsdp_axes, axis=axis, tiled=True)
        return QuantizedTensor(codes=codes, scale=w.scale, zero=w.zero,
                               spec=w.spec, n_in=w.n_in, packed=w.packed,
                               act_scale=w.act_scale)
    return jax.lax.all_gather(w, fsdp_axes, axis=axis, tiled=True)


def _moe_body_sharded(x, router_w, wg, wu, wd, *, cfg: ModelConfig,
                      model_axis: str, fsdp_axes, quantized: bool = False):
    """shard_map body.  Shapes are per-device:
    x (b_loc, T, d); router_w (d, E) replicated; wg/wu (E_loc, d_loc, f);
    wd (E_loc, f, d_loc)."""
    b, t, d = x.shape
    x_flat = x.reshape(-1, d)
    n = b * t
    e_pad = router_w.shape[-1]
    m = int(jax.lax.psum(1, model_axis))  # static axis size (constant-folded)
    e_loc = e_pad // m
    k = cfg.experts_per_token
    cap = _capacity(n, k, cfg.n_experts, cfg.moe_capacity_factor)

    probs, topi, aux = _route(x_flat, router_w, cfg.n_experts, k)
    buf, dest, keep, src, gate = _dispatch(x_flat, topi, probs, e_pad, cap)

    # exchange: (E, C, d) -> (E_loc, m*C, d).  View the buffer as
    # (dest_shard, e_loc, C, d); after all_to_all axis 0 indexes the
    # *source* shard, so entry (j, e, c) is source-shard j's buffer for
    # this shard's local expert e.
    buf = buf.reshape(m, e_loc, cap, d)
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=0,
                             tiled=True)
    buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, m * cap, d)

    # FSDP all-gather of this layer's local expert shards over (pod, data)
    wg_f = _gather_expert_weight(wg, 1, fsdp_axes)
    wu_f = _gather_expert_weight(wu, 1, fsdp_axes)
    wd_f = _gather_expert_weight(wd, 2, fsdp_axes)

    out_buf, _ = _expert_ffn(buf, wg_f, wu_f, wd_f)

    # reverse exchange: rows go back to their source shard; after the
    # all_to_all axis 0 indexes the expert-owner shard, so global expert
    # id e = owner * e_loc + e_local matches the dispatch's block layout.
    out_buf = out_buf.reshape(e_loc, m, cap, d).transpose(1, 0, 2, 3)
    out_buf = jax.lax.all_to_all(out_buf, model_axis, split_axis=0,
                                 concat_axis=0, tiled=True)
    out_buf = out_buf.reshape(e_pad, cap, d)

    y = _combine(out_buf, dest, keep, src, gate, n, d)
    aux = jax.lax.pmean(aux, (model_axis,) + tuple(fsdp_axes))
    return y.reshape(b, t, d), aux


def _expert_specs(w, in_dim_axes, fsdp):
    """Per-leaf shard_map specs for one expert-weight arg.

    FP array: single P.  QuantizedTensor: a matching pytree of specs —
    codes shard like the weight; group scales/zeros and act_scale are
    small and replicated beyond the expert axis."""
    from repro.core.quantizer import QuantizedTensor
    if not isinstance(w, QuantizedTensor):
        return P("model", fsdp, None) if in_dim_axes == 1 \
            else P("model", None, fsdp)
    codes_spec = (P("model", fsdp, None) if in_dim_axes == 1
                  else P("model", None, fsdp))
    meta_spec = P("model", None, None)
    act_spec = None if w.act_scale is None else P("model", None)
    return QuantizedTensor(codes=codes_spec, scale=meta_spec, zero=meta_spec,
                           spec=w.spec, n_in=w.n_in, packed=w.packed,
                           act_scale=act_spec)


def moe_ffn(x, router_w, wg, wu, wd, cfg: ModelConfig, collect: bool = False):
    """Dispatching MoE FFN: shard_map on an active mesh, local otherwise.

    Tokens enter sharded over (batch x **sequence**): the sequence axis is
    split over ``model`` so each device routes only T/model_axis tokens.
    Without this, every model-shard in a data row routes — and, after the
    all-to-all, every expert shard *computes* — the same replicated
    tokens: a model_axis-fold waste of expert FLOPs and exchange bytes
    that dominated the baseline MoE train cells (EXPERIMENTS.md §Perf
    iteration 2).  Sequence positions are independent in an FFN, so
    correctness is unaffected; capacity is per (device, expert) sub-batch.
    """
    mesh = active_mesh()
    if mesh is None or collect or "model" not in mesh.shape:
        return moe_ffn_local(x, router_w, wg, wu, wd, cfg, collect)
    from repro.core.quantizer import QuantizedTensor
    quantized = isinstance(wg, QuantizedTensor)
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    batch_spec = fsdp if fsdp else None
    seq_spec = "model" if x.shape[1] % mesh.shape["model"] == 0 else None
    body = functools.partial(_moe_body_sharded, cfg=cfg, model_axis="model",
                             fsdp_axes=fsdp, quantized=quantized)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_spec, seq_spec, None), P(None, None),
                  _expert_specs(wg, 1, fsdp), _expert_specs(wu, 1, fsdp),
                  _expert_specs(wd, 2, fsdp)),
        out_specs=(P(batch_spec, seq_spec, None), P()),
        check_vma=False,
    )(x, router_w, wg, wu, wd)
    return y, aux, {}


class MoELM(DenseLM):
    """Dense attention + MoE FFN blocks, with optional shared expert(s)."""

    def init(self, key) -> dict:
        cfg = self.cfg
        hd = cfg.head_dim_
        v_pad = padded_vocab(cfg.vocab_size)
        e_pad = padded_experts(cfg.n_experts)
        k_emb, k_blocks, k_head = jax.random.split(key, 3)

        def block_init(k):
            ks = jax.random.split(k, 12)
            p = {
                "attn_norm": jnp.ones((cfg.d_model,), self.dtype),
                "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, self.dtype),
                "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, self.dtype),
                "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, self.dtype),
                "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, self.dtype),
                "mlp_norm": jnp.ones((cfg.d_model,), self.dtype),
                "router": dense_init(ks[4], cfg.d_model, e_pad, self.dtype),
                "wg_exp": jax.random.normal(ks[5], (e_pad, cfg.d_model, cfg.d_ff)).astype(self.dtype) * (cfg.d_model ** -0.5),
                "wu_exp": jax.random.normal(ks[6], (e_pad, cfg.d_model, cfg.d_ff)).astype(self.dtype) * (cfg.d_model ** -0.5),
                "wd_exp": jax.random.normal(ks[7], (e_pad, cfg.d_ff, cfg.d_model)).astype(self.dtype) * (cfg.d_ff ** -0.5),
            }
            if cfg.n_shared_experts:
                f_sh = cfg.shared_expert_ff
                p["wg_sh"] = dense_init(ks[8], cfg.d_model, f_sh, self.dtype)
                p["wu_sh"] = dense_init(ks[9], cfg.d_model, f_sh, self.dtype)
                p["wd_sh"] = dense_init(ks[10], f_sh, cfg.d_model, self.dtype)
            return p

        return {
            "embed": dense_init(k_emb, v_pad, cfg.d_model, self.dtype,
                                scale=0.02),
            "blocks": stack_layer_params(k_blocks, cfg.n_layers, block_init),
            "final_norm": jnp.ones((cfg.d_model,), self.dtype),
            "lm_head": dense_init(k_head, cfg.d_model, v_pad, self.dtype),
        }

    def param_axes(self) -> dict:
        ax = {
            "embed": ("vocab", "fsdp"),
            "blocks": {
                "attn_norm": (None, None),
                "wq": (None, "fsdp", "heads"),
                "wk": (None, "fsdp", None),
                "wv": (None, "fsdp", None),
                "wo": (None, "heads", "fsdp"),
                "mlp_norm": (None, None),
                "router": (None, None, None),
                "wg_exp": (None, "experts", "fsdp", None),
                "wu_exp": (None, "experts", "fsdp", None),
                "wd_exp": (None, "experts", None, "fsdp"),
            },
            "final_norm": (None,),
            "lm_head": ("fsdp", "vocab"),
        }
        if self.cfg.n_shared_experts:
            ax["blocks"].update({
                "wg_sh": (None, "fsdp", "ff"),
                "wu_sh": (None, "fsdp", "ff"),
                "wd_sh": (None, "ff", "fsdp"),
            })
        return ax

    def quant_site_map(self) -> dict:
        m = {
            ("blocks", "wq"): "attn_in",
            ("blocks", "wk"): "attn_in",
            ("blocks", "wv"): "attn_in",
            ("blocks", "wo"): "attn_out",
            ("blocks", "wg_exp"): "mlp_in",
            ("blocks", "wu_exp"): "mlp_in",
            ("blocks", "wd_exp"): "mlp_down",
        }
        if self.cfg.n_shared_experts:
            m.update({
                ("blocks", "wg_sh"): "mlp_in",
                ("blocks", "wu_sh"): "mlp_in",
                ("blocks", "wd_sh"): "shared_down",
            })
        return m

    # override the FFN half of the block
    def _block(self, p, x, positions, collect, *, cache=None, cache_len=None,
               kv_lens=None):
        h = rms_norm(x, p["attn_norm"], self.cfg.norm_eps)
        stats = {}
        if collect:
            stats["attn_in"] = site_stat(h)
        attn_out, kv, o_pre = self._attn(p, h, positions, cache=cache,
                                         cache_len=cache_len, kv_lens=kv_lens)
        if collect:
            stats["attn_out"] = site_stat(o_pre)
        x = x + attn_out
        h = rms_norm(x, p["mlp_norm"], self.cfg.norm_eps)
        if collect:
            stats["mlp_in"] = site_stat(h)
        if cache is not None and h.shape[1] > 1:
            # speculative verify span: route each position separately so
            # the capacity cutoff (a function of the routed token count)
            # matches sequential T=1 decode exactly — pooled routing
            # would let burst tokens compete for expert capacity and
            # drop different tokens than the non-speculative loop
            outs, auxes = [], []
            for i in range(h.shape[1]):
                y_i, aux_i, _ = moe_ffn(h[:, i:i + 1], p["router"],
                                        p["wg_exp"], p["wu_exp"],
                                        p["wd_exp"], self.cfg, False)
                outs.append(y_i)
                auxes.append(aux_i)
            y = jnp.concatenate(outs, axis=1)
            aux = jnp.mean(jnp.stack(auxes))
            moe_stats = {}
        else:
            y, aux, moe_stats = moe_ffn(h, p["router"], p["wg_exp"],
                                        p["wu_exp"], p["wd_exp"], self.cfg,
                                        collect)
        stats.update(moe_stats)
        if self.cfg.n_shared_experts:
            g = qlinear(h, p["wg_sh"])
            u = qlinear(h, p["wu_sh"])
            hidden = jax.nn.silu(g) * u
            hidden = shard_hint(hidden, "batch", "seq", "ff")
            if collect:
                stats["shared_down"] = site_stat(hidden)
            with row_parallel():
                y = y + qlinear(hidden, p["wd_sh"])
        x = x + y
        x = shard_hint(x, "batch", "seq", "embed")
        return x, kv, stats, aux

    # scan wrappers must thread the aux loss through
    def forward(self, params, batch, collect_stats: bool = False):
        tokens = batch["tokens"]
        b, t = tokens.shape
        positions = self._positions(batch, b, t)
        x = embed_tokens(params["embed"], tokens).astype(self.dtype)
        x = shard_hint(x, "batch", "seq", "embed")

        def body(x, p):
            x, _, stats, aux = self._block(p, x, positions, collect_stats)
            return x, (stats if collect_stats else None, aux)

        if self.cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, (stats, aux) = layer_scan(body, x, params["blocks"])
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = logits_from_hidden(x, params["lm_head"], self.cfg.vocab_size)
        out = {"stats": stats if collect_stats else {},
               "moe_aux": jnp.mean(aux)}
        return logits, out

    def prefill(self, params, tokens, cache, prompt_len=None):
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        if prompt_len is None:
            plen = jnp.full((b,), t, jnp.int32)
            kv_lens = None
        else:
            plen = jnp.broadcast_to(prompt_len, (b,)).astype(jnp.int32)
            kv_lens = plen
        x = embed_tokens(params["embed"], tokens).astype(self.dtype)
        x = shard_hint(x, "batch", "seq", "embed")

        def body(x, xs):
            p, kc, vc = xs
            x, (k, v), _, _ = self._block(p, x, positions, False,
                                          kv_lens=kv_lens)
            kc = jax.lax.dynamic_update_slice(
                kc, k.transpose(0, 2, 1, 3), (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v.transpose(0, 2, 1, 3), (0, 0, 0, 0))
            return x, (kc, vc)

        x, (kc, vc) = layer_scan(body, x, (params["blocks"], cache["k"],
                                             cache["v"]))
        x = x[:, -1:] if prompt_len is None else last_valid_hidden(x, plen)
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = logits_from_hidden(x, params["lm_head"], self.cfg.vocab_size)
        return logits, {"k": kc, "v": vc, "len": plen}

    def decode_step(self, params, cache, token, pos=None):
        """One decode step; token (B, T) with T > 1 the speculative
        verify span (same contract as :meth:`DenseLM.decode_step` — the
        span write and verify attention live in the inherited
        ``_attn``)."""
        b, t = token.shape
        base = cache["len"].astype(jnp.int32)
        new_len = base + t
        positions = base[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        x = embed_tokens(params["embed"], token).astype(self.dtype)
        x = shard_hint(x, "batch", "seq", "embed")

        def body(x, xs):
            p, kc, vc = xs
            x, (kc, vc), _, _ = self._block(p, x, positions, False,
                                            cache=(kc, vc), cache_len=new_len)
            return x, (kc, vc)

        x, (kc, vc) = layer_scan(body, x, (params["blocks"], cache["k"],
                                             cache["v"]))
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = logits_from_hidden(x, params["lm_head"], self.cfg.vocab_size)
        return logits, {"k": kc, "v": vc, "len": new_len}

    def supports_spec(self) -> bool:
        """MoE overrides the dense decode pair but keeps the same cache
        layout and span-write attention, so speculative verification
        works; further subclasses that override it again decline."""
        return (type(self).prefill is MoELM.prefill
                and type(self).decode_step is MoELM.decode_step)
