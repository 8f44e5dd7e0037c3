"""Model-level quantization: apply RTN / AWQ / FAQ to a full parameter tree.

Models expose ``quant_site_map() -> {param_path: site_key}`` where each
mapped leaf has shape ``(L, [extra...], n_in, n_out)`` (layer-stacked for
scan; MoE adds an experts dim) and ``stats[site_key]["mean_abs"]`` is
``(L, n_in)``.  Because all per-layer weights are stacked, whole-model
quantization is one ``lax.map`` over layers per site — and trivially
layer-parallel in the distributed path.

Two output modes:

* ``"fake"``   — same-structure params with each quantized weight replaced
  by its dequantized reconstruction (runs through the unchanged model;
  used by evaluation benchmarks).
* ``"packed"`` — quantized leaves become :class:`QuantizedTensor` (packed
  uint8 codes + group scales + act_scale); the model's linear dispatch
  routes these through the dequant-matmul kernel (serving path).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .methods import (DEFAULT_ALPHA_GRID, PRESEARCHED_GAMMA,
                      PRESEARCHED_WINDOW, search_alpha, site_stat_for_method)
from .quantizer import QuantSpec, quant_dequant, quantize_groupwise


def _get_path(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set_path(tree, path, value):
    if len(path) == 1:
        out = dict(tree)
        out[path[0]] = value
        return out
    out = dict(tree)
    out[path[0]] = _set_path(tree[path[0]], path[1:], value)
    return out


def _quantize_leaf(w, stat, spec, alpha_grid, loss, stats_site, mode):
    """Quantize one (L, [extra...], n_in, n_out) leaf.

    ``stat`` is the (L, n_in) method statistic or None (RTN).
    Returns (new_leaf, report_dict).
    """
    if mode not in ("fake", "packed"):
        raise ValueError(f"unknown mode {mode!r}")
    mean_sq = sample = None
    if stat is not None:
        mean_sq = stats_site["mean_sq"] if loss == "diag" else None
        sample = stats_site["sample"] if loss == "sample" else None
    return _quantize_layers(w, stat, mean_sq, sample, spec=spec,
                            alpha_grid=tuple(alpha_grid), mode=mode)


@partial(jax.jit, static_argnames=("spec", "alpha_grid", "mode"))
def _quantize_layers(w, stat, mean_sq, sample, *, spec, alpha_grid, mode):
    """α search and quantization of an (L, [extra...], n_in, n_out) stack,
    one layer at a time (``lax.map``): only one layer's f32 temporaries
    are live, so the peak stays that of a layer however deep the model
    is.  The reshapes stay inside the program, where they copy nothing."""
    L = w.shape[0]
    n_in, n_out = w.shape[-2], w.shape[-1]
    extra = w.shape[1:-2]

    def one_layer(xs):
        w_l, stat_l, msq_l, smp_l = xs                # w_l (E, n_in, n_out)
        if stat_l is None:                            # RTN
            act, report = None, {}
        else:
            res = jax.vmap(lambda w2: search_alpha(
                w2, stat_l, spec, alpha_grid, mean_sq=msq_l,
                sample=smp_l))(w_l)
            act = res.act_scale                       # (E, n_in)
            report = {"alpha": res.alpha, "loss": res.loss,
                      "rtn_loss": res.rtn_loss}
        if mode == "fake":
            if act is None:
                out = jax.vmap(lambda x: quant_dequant(x, spec))(w_l)
            else:
                out = jax.vmap(lambda x, s: quant_dequant(
                    x, spec, act_scale=s))(w_l, act)
            out = out.astype(w_l.dtype)
        elif act is None:
            out = jax.vmap(lambda x: quantize_groupwise(
                x, spec, pack=True))(w_l)
        else:
            out = jax.vmap(lambda x, s: quantize_groupwise(
                x, spec, act_scale=s, pack=True))(w_l, act)
        return out, report

    w_flat = w.reshape((L, -1, n_in, n_out))
    new_leaf, report = jax.lax.map(one_layer, (w_flat, stat, mean_sq, sample))
    # back from (L, E, ...) to (L, *extra, ...)
    new_leaf = jax.tree_util.tree_map(
        lambda a: a.reshape((L,) + extra + a.shape[2:]), new_leaf)
    return new_leaf, report


def quantize_model(params: dict, site_map: dict, stats: dict, *,
                   method: str = "faq",
                   spec: QuantSpec = QuantSpec(),
                   gamma: float = PRESEARCHED_GAMMA,
                   window: int = PRESEARCHED_WINDOW,
                   loss: str = "sample",
                   mode: str = "fake",
                   alpha_grid: tuple = DEFAULT_ALPHA_GRID):
    """Quantize every site-mapped leaf of ``params``.

    Returns ``(new_params, report)`` with ``report[path_str]`` holding the
    per-layer chosen α and losses (empty for RTN).
    """
    new_params = params
    report = {}
    for path, site_key in site_map.items():
        w = _get_path(params, path)
        stats_site = stats[site_key] if stats is not None else None
        if method == "rtn":
            stat = None
        else:
            stat = site_stat_for_method(method, stats_site["mean_abs"],
                                        gamma=gamma, window=window)
        new_leaf, rep = _quantize_leaf(w, stat, spec, alpha_grid, loss,
                                       stats_site, mode)
        new_params = _set_path(new_params, path, new_leaf)
        report["/".join(path)] = rep
    return new_params, report


def report_summary(report: dict) -> dict:
    """Aggregate per-site report into scalars for logging/benchmarks."""
    out = {}
    for path, rep in report.items():
        if not rep:
            continue
        loss = float(jnp.mean(rep["loss"]))
        rtn = float(jnp.mean(rep["rtn_loss"]))
        out[path] = {
            "mean_alpha": float(jnp.mean(rep["alpha"])),
            "mean_loss": loss,
            "mean_rtn_loss": rtn,
            "improvement_vs_rtn": (rtn - loss) / max(rtn, 1e-30),
        }
    return out
