"""Draft sources for speculative decoding (DESIGN.md §12).

A *draft* proposes K cheap tokens per engine step; the target model
verifies them in one batched forward.  Two pluggable sources:

* :class:`SelfDraft` — the FAQ int8 quantization of the *target's own*
  weights.  The paper's central property (FAQ-calibrated quantized
  models track the full-precision model's future activations) is
  exactly what a draft needs for high acceptance, and the draft shares
  the target's architecture, cache layout, and KV pages: the draft
  writes its speculative K/V straight into the target cache and the
  verify pass overwrites those positions with target K/V, so the
  self-draft costs **zero extra KV memory**.  Its weights stay int8
  codes plus group scales in HBM (half the bytes of bf16; at llama3-8b
  widths a dense bf16 copy would not fit one 16 GB chip beside the
  int4 target) and run through the dequant-matmul dispatch.

* :class:`ModelDraft` — any smaller registry model as an independent
  draft with its own small dense KV cache.  Acceptance depends entirely
  on how well the draft tracks the target; correctness never does — the
  verify/accept rule guarantees the emitted stream is an exact sample
  from the target policy even for a random draft.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import QuantSpec, quantize_model
from repro.core.apply import _get_path, _set_path
from repro.core.quantizer import QuantizedTensor, dequantize_groupwise


class _Placeable:
    """Sharded-serving hook shared by all draft sources: a tensor-parallel
    engine re-places the draft's weights on its mesh with the *same*
    logical-axis annotations as the resolved draft model, so draft burst
    steps run under the identical TP layout (and collective pattern) as
    the target's decode step."""

    def place(self, place_fn, dmodel):
        axes = (dmodel.param_axes()
                if hasattr(dmodel, "param_axes") else None)
        self.params = place_fn(self.params, axes)


@dataclasses.dataclass
class SelfDraft(_Placeable):
    """Self-draft: the target model running int8-FAQ'd target weights.

    ``model`` stays ``None`` — the runner resolves it to the engine's
    target model, and the draft shares the target's dense cache or
    paged KV store (speculative writes are overwritten by verify).
    """
    params: Any
    bits: int = 8
    shares_cache = True
    model = None


@dataclasses.dataclass
class ModelDraft(_Placeable):
    """Independent draft model with its own dense KV cache."""
    model: Any
    params: Any
    shares_cache = False


@functools.partial(jax.jit, static_argnames="dtype")  # repro: noqa[RPR001] build-time weight transform, before any engine or mesh exists
def _materialize(qt, dtype):
    """Dense original-domain reconstruction of one QuantizedTensor leaf.

    Param-tree leaves carry stacked leading axes (layers, experts): the
    layer axis is walked with ``lax.map``, so one layer's f32 dequant is
    live at a time, and the rest are vmapped.  ``act_scale`` is folded
    back in (``(x/s) @ deq(codes)  ==  x @ (deq(codes) / s[:, None])``),
    so the result is the exact weight the serving dequant-matmul
    realizes, rounded to ``dtype``.
    """
    def deq2(sub):
        w = dequantize_groupwise(dataclasses.replace(sub, act_scale=None))
        if sub.act_scale is not None:
            w = w / sub.act_scale[:, None]
        return w.astype(dtype)

    fn = deq2
    for _ in range(qt.codes.ndim - 3):
        fn = jax.vmap(fn)
    return jax.lax.map(fn, qt)


def self_int8_draft(model, params, stats=None, *, bits: int = 8,
                    group_size: int = 64) -> SelfDraft:
    """Build the FAQ int8 self-draft from the target's weights.

    ``params`` may be the fp weights *or* the packed serving tree —
    QuantizedTensor leaves are first materialized to the exact weights
    the serving dequant-matmul realizes, so the draft is the int8
    quantization of **the model being served** (derived purely from the
    codes that already exist at serve time): its greedy argmaxes track
    the target's almost everywhere, which is what acceptance rate pays
    for.  ``stats`` are the same calibration statistics used to
    quantize the serving weights (FAQ's future-activation preview);
    without them the draft falls back to plain RTN int8.  One site is
    materialized at a time, so the peak holds one dense leaf beside the
    two quantized trees.
    """
    method = "faq" if stats is not None else "rtn"
    spec = QuantSpec(bits=bits, group_size=group_size)
    dtype = jnp.dtype(model.cfg.dtype)
    qp = params
    for path, site in model.quant_site_map().items():
        leaf = _get_path(qp, path)
        if isinstance(leaf, QuantizedTensor):
            qp = _set_path(qp, path, _materialize(leaf, dtype))
        qp, _ = quantize_model(qp, {path: site}, stats, method=method,
                               spec=spec, mode="packed")
    return SelfDraft(params=qp, bits=bits)


def registry_draft(arch: str, *, tiny: bool = True, seed: int = 0,
                   params: Optional[Any] = None) -> ModelDraft:
    """Build an independent draft from a registry architecture name.

    With ``params=None`` the draft is randomly initialized — useful as
    plumbing (greedy output is still exactly the target's; acceptance
    is just poor), real deployments pass trained/distilled weights.
    """
    from repro.configs import ARCHS
    from repro.models.registry import build_model

    cfg = ARCHS[arch].tiny() if tiny else ARCHS[arch]
    model = build_model(cfg)
    if not getattr(model, "supports_spec", lambda: False)():
        raise ValueError(
            f"draft arch {arch!r} ({cfg.family}) lacks the span-write "
            "decode path speculative drafting needs")
    if params is None:
        params = model.init(jax.random.PRNGKey(seed))
    return ModelDraft(model=model, params=params)
