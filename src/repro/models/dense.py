"""Dense llama-style decoder LM (stablelm / llama3 / deepseek-coder).

Functional style: ``init`` builds a nested-dict param tree with per-layer
weights stacked on a leading L axis; ``forward``/``prefill``/``decode_step``
scan over layers.  KV cache layout is ``(L, B, KH, S, hd)`` — kv-heads
before sequence so the sharding-hint priority picks head-sharding when the
head count divides the model axis and falls back to sequence sharding
otherwise (see dist/sharding.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.stats import site_stat
from repro.dist.sharding import row_parallel, shard_hint
from repro.kernels.ops import (decode_attention, decode_attention_q8,
                               paged_decode_attention,
                               paged_decode_attention_q8,
                               paged_verify_attention,
                               paged_verify_attention_q8, verify_attention,
                               verify_attention_q8)
from .common import (layer_scan,
                     apply_rope, chunked_attention, quantize_kv,
                     dense_init, embed_tokens, last_valid_hidden,
                     logits_from_hidden,
                     padded_vocab, qlinear, rms_norm,
                     stack_layer_params, update_cache_at, update_pages_at)


class DenseLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)

    # -- params ------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        hd = cfg.head_dim_
        v_pad = padded_vocab(cfg.vocab_size)
        k_emb, k_blocks, k_head = jax.random.split(key, 3)

        def block_init(k):
            ks = jax.random.split(k, 7)
            return {
                "attn_norm": jnp.ones((cfg.d_model,), self.dtype),
                "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, self.dtype),
                "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, self.dtype),
                "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, self.dtype),
                "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, self.dtype),
                "mlp_norm": jnp.ones((cfg.d_model,), self.dtype),
                "w_gate": dense_init(ks[4], cfg.d_model, cfg.d_ff, self.dtype),
                "w_up": dense_init(ks[5], cfg.d_model, cfg.d_ff, self.dtype),
                "w_down": dense_init(ks[6], cfg.d_ff, cfg.d_model, self.dtype),
            }

        return {
            "embed": dense_init(k_emb, v_pad, cfg.d_model, self.dtype,
                                scale=0.02),
            "blocks": stack_layer_params(k_blocks, cfg.n_layers, block_init),
            "final_norm": jnp.ones((cfg.d_model,), self.dtype),
            "lm_head": dense_init(k_head, cfg.d_model, v_pad, self.dtype),
        }

    def param_axes(self) -> dict:
        return {
            "embed": ("vocab", "fsdp"),
            "blocks": {
                "attn_norm": (None, None),
                "wq": (None, "fsdp", "heads"),
                "wk": (None, "fsdp", None),
                "wv": (None, "fsdp", None),
                "wo": (None, "heads", "fsdp"),
                "mlp_norm": (None, None),
                "w_gate": (None, "fsdp", "ff"),
                "w_up": (None, "fsdp", "ff"),
                "w_down": (None, "ff", "fsdp"),
            },
            "final_norm": (None,),
            "lm_head": ("fsdp", "vocab"),
        }

    def quant_site_map(self) -> dict:
        return {
            ("blocks", "wq"): "attn_in",
            ("blocks", "wk"): "attn_in",
            ("blocks", "wv"): "attn_in",
            ("blocks", "wo"): "attn_out",
            ("blocks", "w_gate"): "mlp_in",
            ("blocks", "w_up"): "mlp_in",
            ("blocks", "w_down"): "mlp_down",
        }

    # -- block -------------------------------------------------------------
    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            x = embed_tokens(params["embed"], tokens).astype(self.dtype)
            return shard_hint(x, "batch", "seq", "embed")

    def _head(self, params, x):
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
            return logits_from_hidden(x, params["lm_head"],
                                      self.cfg.vocab_size)

    def _attn(self, p, x, positions, *, kv_write=None, cache=None,
              cache_len=None, kv_lens=None, paged=None):
        """Attention sub-block.  Returns (out, (k, v)) — k/v as produced
        (for prefill cache capture).

        ``paged`` switches decode to the paged KV store: a
        ``(page_table, page_ids, offsets)`` triple, with ``cache``
        holding this layer's physical page-store leaves instead of
        dense per-slot caches (see DESIGN.md §10).
        """
        cfg = self.cfg
        hd = cfg.head_dim_
        b, t, _ = x.shape
        q = qlinear(x, p["wq"]).reshape(b, t, cfg.n_heads, hd)
        k = qlinear(x, p["wk"]).reshape(b, t, cfg.n_kv_heads, hd)
        v = qlinear(x, p["wv"]).reshape(b, t, cfg.n_kv_heads, hd)
        q = apply_rope(q, positions, cfg.rope_theta,
                       mrope_sections=cfg.mrope_sections or None)
        k = apply_rope(k, positions, cfg.rope_theta,
                       mrope_sections=cfg.mrope_sections or None)
        q = shard_hint(q, "batch", "seq", "heads", None)
        k = shard_hint(k, "batch", "seq", "kv_heads", None)
        v = shard_hint(v, "batch", "seq", "kv_heads", None)
        if paged is not None:
            # page_ids/offsets are (B, T): the span t > 1 (speculative
            # verify) may cross a page boundary, so each position writes
            # through its own physical page.
            table, page_ids, offsets = paged
            window = cfg.sliding_window or None
            if cfg.kv_cache_bits == 8:
                k_st, ks_st, v_st, vs_st = cache
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                kq, ks = kq.transpose(0, 2, 1, 3), ks.transpose(0, 2, 1, 3)
                vq, vs = vq.transpose(0, 2, 1, 3), vs.transpose(0, 2, 1, 3)
                with jax.named_scope("kv_write"):
                    for i in range(t):
                        at = (page_ids[:, i], offsets[:, i])
                        k_st = update_pages_at(k_st, kq[:, :, i:i + 1], *at)
                        ks_st = update_pages_at(ks_st, ks[:, :, i:i + 1], *at)
                        v_st = update_pages_at(v_st, vq[:, :, i:i + 1], *at)
                        vs_st = update_pages_at(vs_st, vs[:, :, i:i + 1], *at)
                if t == 1:
                    o = paged_decode_attention_q8(q, k_st, ks_st, v_st,
                                                  vs_st, table, cache_len,
                                                  window=window)
                else:
                    o = paged_verify_attention_q8(q, k_st, ks_st, v_st,
                                                  vs_st, table,
                                                  cache_len - t,
                                                  window=window)
                kv = (k_st, ks_st, v_st, vs_st)
            else:
                k_st, v_st = cache
                kt = k.transpose(0, 2, 1, 3)
                vt = v.transpose(0, 2, 1, 3)
                with jax.named_scope("kv_write"):
                    for i in range(t):
                        at = (page_ids[:, i], offsets[:, i])
                        k_st = update_pages_at(k_st, kt[:, :, i:i + 1], *at)
                        v_st = update_pages_at(v_st, vt[:, :, i:i + 1], *at)
                if t == 1:
                    o = paged_decode_attention(q, k_st, v_st, table,
                                               cache_len, window=window)
                else:
                    o = paged_verify_attention(q, k_st, v_st, table,
                                               cache_len - t, window=window)
                kv = (k_st, v_st)
            o = o.reshape(b, t, cfg.n_heads * hd)
            with row_parallel():
                out = qlinear(o, p["wo"])
            return out, kv, o
        if cache is None:
            window = cfg.sliding_window or None
            o = chunked_attention(q, k, v, causal=True, window=window,
                                  kv_lens=kv_lens)
        elif cfg.kv_cache_bits == 8:
            k_cache, k_sc, v_cache, v_sc = cache
            pos = cache_len - t        # span start; t=1 is plain decode
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            with jax.named_scope("kv_write"):
                k_cache = update_cache_at(k_cache, kq.transpose(0, 2, 1, 3),
                                          pos)
                v_cache = update_cache_at(v_cache, vq.transpose(0, 2, 1, 3),
                                          pos)
                k_sc = update_cache_at(k_sc, ks.transpose(0, 2, 1, 3), pos)
                v_sc = update_cache_at(v_sc, vs.transpose(0, 2, 1, 3), pos)
            window = cfg.sliding_window or None
            if t == 1:
                o = decode_attention_q8(q, k_cache, k_sc, v_cache, v_sc,
                                        cache_len, window=window)
            else:
                o = verify_attention_q8(q, k_cache, k_sc, v_cache, v_sc,
                                        cache_len - t, window=window)
            k, v = (k_cache, k_sc), (v_cache, v_sc)
        else:
            k_cache, v_cache = cache  # (B, KH, S, hd)
            pos = cache_len - t           # (B,) span start
            with jax.named_scope("kv_write"):
                k_cache = update_cache_at(k_cache, k.transpose(0, 2, 1, 3),
                                          pos)
                v_cache = update_cache_at(v_cache, v.transpose(0, 2, 1, 3),
                                          pos)
            window = cfg.sliding_window or None
            if t == 1:
                o = decode_attention(q, k_cache, v_cache, cache_len,
                                     window=window)
            else:
                o = verify_attention(q, k_cache, v_cache, cache_len - t,
                                     window=window)
            k, v = k_cache, v_cache
        o = o.reshape(b, t, cfg.n_heads * hd)
        with row_parallel():
            out = qlinear(o, p["wo"])
        return out, (k, v), o

    def _block(self, p, x, positions, collect, *, cache=None, cache_len=None,
               kv_lens=None, paged=None):
        stats = {}
        with jax.named_scope("attn"):
            h = rms_norm(x, p["attn_norm"], self.cfg.norm_eps)
            if collect:
                stats["attn_in"] = site_stat(h)
            attn_out, kv, o_pre = self._attn(p, h, positions, cache=cache,
                                             cache_len=cache_len,
                                             kv_lens=kv_lens, paged=paged)
            if collect:
                stats["attn_out"] = site_stat(o_pre)
            x = x + attn_out
        with jax.named_scope("mlp"):
            h = rms_norm(x, p["mlp_norm"], self.cfg.norm_eps)
            if collect:
                stats["mlp_in"] = site_stat(h)
            g = qlinear(h, p["w_gate"])
            u = qlinear(h, p["w_up"])
            hidden = jax.nn.silu(g) * u
            hidden = shard_hint(hidden, "batch", "seq", "ff")
            if collect:
                stats["mlp_down"] = site_stat(hidden)
            with row_parallel():
                x = x + qlinear(hidden, p["w_down"])
            x = shard_hint(x, "batch", "seq", "embed")
        return x, kv, stats

    # -- entry points --------------------------------------------------------
    def forward(self, params, batch, collect_stats: bool = False):
        """Full causal forward (training / evaluation).

        Returns (logits, aux) with aux = {"stats": ..., "moe_aux": scalar}
        — the uniform contract across all model families."""
        tokens = batch["tokens"]
        b, t = tokens.shape
        positions = self._positions(batch, b, t)
        x = self._embed(params, tokens)

        def body(x, p):
            x, _, stats = self._block(p, x, positions, collect_stats)
            return x, (stats if collect_stats else None)

        if self.cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, stats = layer_scan(body, x, params["blocks"])
        logits = self._head(params, x)
        aux = {"stats": stats if collect_stats else {},
               "moe_aux": jnp.zeros((), jnp.float32)}
        return logits, aux

    def prefill(self, params, tokens, cache, prompt_len=None):
        """Run the prompt and write the KV cache in-place (functional).

        cache: dict(k=(L,B,KH,S,hd), v=..., len=()) with S >= T.
        ``prompt_len`` (B,) int32 marks each row's true prompt length for
        bucket-padded batched prefill: keys at positions >= prompt_len[b]
        are masked (length-aware causal mask), the returned logits are
        each row's *last valid* position, and cache["len"] is per-batch
        so decode continues from the right slot position.  ``None`` keeps
        the dense full-length behavior (every row is exactly T long).
        Returns (logits_last, cache)."""
        b, t = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        positions = self._maybe_mrope(positions)
        if prompt_len is None:
            plen = jnp.full((b,), t, jnp.int32)
            kv_lens = None
        else:
            plen = jnp.broadcast_to(prompt_len, (b,)).astype(jnp.int32)
            kv_lens = plen
        x = self._embed(params, tokens)

        if self.cfg.kv_cache_bits == 8:
            def body8(x, xs):
                p, kc, ksc, vc, vsc = xs
                x, (k, v), _ = self._block(p, x, positions, False,
                                           kv_lens=kv_lens)
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                with jax.named_scope("kv_write"):
                    kc = jax.lax.dynamic_update_slice(
                        kc, kq.transpose(0, 2, 1, 3), (0, 0, 0, 0))
                    ksc = jax.lax.dynamic_update_slice(
                        ksc, ks.transpose(0, 2, 1, 3), (0, 0, 0, 0))
                    vc = jax.lax.dynamic_update_slice(
                        vc, vq.transpose(0, 2, 1, 3), (0, 0, 0, 0))
                    vsc = jax.lax.dynamic_update_slice(
                        vsc, vs.transpose(0, 2, 1, 3), (0, 0, 0, 0))
                return x, (kc, ksc, vc, vsc)

            x, (kc, ksc, vc, vsc) = layer_scan(
                body8, x, (params["blocks"], cache["k"], cache["k_scale"],
                           cache["v"], cache["v_scale"]))
            x = x[:, -1:] if prompt_len is None else last_valid_hidden(x, plen)
            logits = self._head(params, x)
            return logits, {"k": kc, "k_scale": ksc, "v": vc,
                            "v_scale": vsc, "len": plen}

        def body(x, xs):
            p, kc, vc = xs
            x, (k, v), _ = self._block(p, x, positions, False,
                                       kv_lens=kv_lens)
            with jax.named_scope("kv_write"):
                kc = jax.lax.dynamic_update_slice(
                    kc, k.transpose(0, 2, 1, 3), (0, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    vc, v.transpose(0, 2, 1, 3), (0, 0, 0, 0))
            return x, (kc, vc)

        x, (kc, vc) = layer_scan(body, x, (params["blocks"], cache["k"],
                                             cache["v"]))
        x = x[:, -1:] if prompt_len is None else last_valid_hidden(x, plen)
        logits = self._head(params, x)
        return logits, {"k": kc, "v": vc, "len": plen}

    def decode_step(self, params, cache, token, pos=None):
        """One decode step.  token: (B, T) int32 with T >= 1 — T = 1 is
        the plain decode hot loop; T > 1 is the speculative K-token
        verify forward (DESIGN.md §12): the T fresh K/V entries are
        written as one span starting at each slot's ``len`` and scored
        with shifted-causal verify attention, so ``logits[:, i]`` is the
        target's next-token distribution after consuming ``token[:, :i+1]``.
        Returns (logits (B, T, V), cache).  cache["len"] is per-batch
        (B,) so slots may hold different-length sequences (continuous
        batching); it advances by T."""
        b, t = token.shape
        base = cache["len"].astype(jnp.int32)           # (B,)
        new_len = base + t
        positions = base[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        positions = self._maybe_mrope(positions)
        x = self._embed(params, token)

        if self.cfg.kv_cache_bits == 8:
            def body8(x, xs):
                p, kc, ksc, vc, vsc = xs
                x, ((kc, ksc), (vc, vsc)), _ = self._block(
                    p, x, positions, False, cache=(kc, ksc, vc, vsc),
                    cache_len=new_len)
                return x, (kc, ksc, vc, vsc)

            x, (kc, ksc, vc, vsc) = layer_scan(
                body8, x, (params["blocks"], cache["k"], cache["k_scale"],
                           cache["v"], cache["v_scale"]))
            logits = self._head(params, x)
            return logits, {"k": kc, "k_scale": ksc, "v": vc,
                            "v_scale": vsc, "len": new_len}

        def body(x, xs):
            p, kc, vc = xs
            x, (kc, vc), _ = self._block(p, x, positions, False,
                                         cache=(kc, vc), cache_len=new_len)
            return x, (kc, vc)

        x, (kc, vc) = layer_scan(body, x, (params["blocks"], cache["k"],
                                             cache["v"]))
        logits = self._head(params, x)
        return logits, {"k": kc, "v": vc, "len": new_len}

    def decode_step_paged(self, params, store, token, page_table, lens):
        """One decode step against the paged KV store.

        store: page-store tree from :meth:`init_paged_cache` (leaves
        (L, P, KH, ps, d) — no ``len``/table leaves, those are
        host-managed); token: (B, T) int32 (T = 1 plain decode, T > 1
        the speculative verify span, as in :meth:`decode_step`);
        page_table: (B, NP) int32 physical ids; lens: (B,) int32 valid
        entries *before* this step (fresh K/V position ``i`` is written
        at offset ``(lens[b]+i) % ps`` of page
        ``page_table[b, (lens[b]+i)//ps]`` — the span may cross a page
        boundary, so ids/offsets are resolved per position).
        Returns (logits, store).  The page table is shared across layers
        — one table per slot addresses every layer's pages.
        """
        t = token.shape[1]
        lens = jnp.broadcast_to(lens, (token.shape[0],)).astype(jnp.int32)
        new_len = lens + t
        pos2d = lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        positions = self._maybe_mrope(pos2d)
        ps = store["k"].shape[3]
        page_ids = jnp.take_along_axis(page_table, pos2d // ps, axis=1)
        offsets = pos2d % ps
        paged = (page_table, page_ids, offsets)
        x = self._embed(params, token)

        if self.cfg.kv_cache_bits == 8:
            def body8(x, xs):
                p, kc, ksc, vc, vsc = xs
                x, (kc, ksc, vc, vsc), _ = self._block(
                    p, x, positions, False, cache=(kc, ksc, vc, vsc),
                    cache_len=new_len, paged=paged)
                return x, (kc, ksc, vc, vsc)

            x, (kc, ksc, vc, vsc) = layer_scan(
                body8, x, (params["blocks"], store["k"], store["k_scale"],
                           store["v"], store["v_scale"]))
            logits = self._head(params, x)
            return logits, {"k": kc, "k_scale": ksc, "v": vc, "v_scale": vsc}

        def body(x, xs):
            p, kc, vc = xs
            x, (kc, vc), _ = self._block(p, x, positions, False,
                                         cache=(kc, vc), cache_len=new_len,
                                         paged=paged)
            return x, (kc, vc)

        x, (kc, vc) = layer_scan(body, x, (params["blocks"], store["k"],
                                             store["v"]))
        logits = self._head(params, x)
        return logits, {"k": kc, "v": vc}

    # -- speculative verify (DESIGN.md §12) --------------------------------
    def verify_step(self, params, cache, tokens):
        """Score a K+1-token speculative burst in one forward pass.

        ``tokens`` (B, K+1) is the last committed token followed by the
        draft proposals; each slot's burst starts at its own
        ``cache["len"]`` (per-slot kv_lens — slots at different
        acceptance depths share the batch).  Writes the burst's K/V span
        into the cache and returns (logits (B, K+1, V), cache) with
        ``len`` advanced by K+1; the engine rolls rejected suffixes back
        via :func:`~repro.serve.cache_ops.truncate_slot`.  This is
        :meth:`decode_step`'s T > 1 form, named for the call site."""
        return self.decode_step(params, cache, tokens)

    def verify_step_paged(self, params, store, tokens, page_table, lens):
        """Paged form of :meth:`verify_step`: the burst span writes
        through per-position physical pages (already allocated and
        exclusively owned by the engine — copy-on-write happens
        host-side first) and rejected-suffix pages are trimmed
        refcount-safely by the engine."""
        return self.decode_step_paged(params, store, tokens, page_table,
                                      lens)

    def supports_spec(self) -> bool:
        """Speculative verification relies on this class's span-write
        decode path; subclasses that override it (hymba's ring buffer,
        recurrent xlstm, VLM's patched prefill) decline and serve
        non-speculatively."""
        return (type(self).prefill is DenseLM.prefill
                and type(self).decode_step is DenseLM.decode_step)

    # -- cache -------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        hd = cfg.head_dim_
        shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, hd)
        if cfg.kv_cache_bits == 8:
            sshape = shape[:-1] + (1,)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v": jnp.zeros(shape, jnp.int8),
                    "v_scale": jnp.zeros(sshape, jnp.float32),
                    "len": jnp.zeros((batch,), jnp.int32)}
        return {"k": jnp.zeros(shape, self.dtype),
                "v": jnp.zeros(shape, self.dtype),
                "len": jnp.zeros((batch,), jnp.int32)}

    def init_paged_cache(self, n_pages: int, page_size: int) -> dict:
        """Physical page store: ``n_pages`` fixed-size KV pages shared by
        all slots through per-slot page tables (serve/pages.py owns the
        allocator; the table and per-slot lengths stay host-side, so the
        tree carries no ``len`` leaf)."""
        cfg = self.cfg
        hd = cfg.head_dim_
        shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, hd)
        if cfg.kv_cache_bits == 8:
            sshape = shape[:-1] + (1,)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v": jnp.zeros(shape, jnp.int8),
                    "v_scale": jnp.zeros(sshape, jnp.float32)}
        return {"k": jnp.zeros(shape, self.dtype),
                "v": jnp.zeros(shape, self.dtype)}

    def supports_paged(self) -> bool:
        """Paged serving relies on this class's exact prefill/decode
        cache layout; subclasses that override either (hymba's ring
        buffer, xlstm's recurrent state, MoE/VLM entry points) fall back
        to the dense cache automatically."""
        return (type(self).prefill is DenseLM.prefill
                and type(self).decode_step is DenseLM.decode_step)

    def cache_axes(self) -> dict:
        ax = (None, "batch", "kv_heads", "kv_seq", None)
        if self.cfg.kv_cache_bits == 8:
            return {"k": ax, "k_scale": ax, "v": ax, "v_scale": ax,
                    "len": None}
        return {"k": ax, "v": ax, "len": None}

    def paged_cache_axes(self) -> dict:
        """Logical axes for :meth:`init_paged_cache` leaves
        (L, P, KH, ps, hd): pages replicated (any slot's table may point
        anywhere), KV heads sharded on the model axis — the same head
        split the dense cache and the attention shard_map use."""
        ax = (None, None, "kv_heads", None, None)
        if self.cfg.kv_cache_bits == 8:
            return {"k": ax, "k_scale": ax, "v": ax, "v_scale": ax}
        return {"k": ax, "v": ax}

    # -- helpers -----------------------------------------------------------
    def _maybe_mrope(self, positions):
        if self.cfg.mrope_sections:
            return jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return positions

    def _positions(self, batch, b, t):
        if "positions" in batch:
            return batch["positions"]
        return self._maybe_mrope(jnp.broadcast_to(jnp.arange(t), (b, t)))
