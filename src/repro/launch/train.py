"""Production training entry point.

Builds the mesh from the available devices (production 16x16 / 2x16x16
on pods; whatever is present elsewhere — a single CPU device degrades to
local training, which is how this container runs it), shards params and
optimizer state via the logical-axis rules, and runs the checkpointed
training loop with automatic resume and elastic re-mesh planning.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --tiny \
        --steps 200 --ckpt-dir reports/launch_train
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPE_CELLS
from repro.data.synthetic import DataConfig, SyntheticLM
from repro.dist import checkpoint as ckpt
from repro.dist.elastic import plan_mesh
from repro.dist.sharding import axis_rules, tree_shardings
from repro.launch import specs as S
from repro.launch.mesh import auto_mesh
from repro.models.registry import build_model
from repro.train.trainer import TrainConfig, make_train_step


def build_mesh():
    n = len(jax.devices())
    if n == 1:
        return None
    plan = plan_mesh(n, model=min(16, n), old_data=max(1, n // 16))
    import numpy as np
    devices = jax.devices()[:plan.used_chips]
    return auto_mesh((plan.data, plan.model), ("data", "model"), devices)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(SHAPE_CELLS))
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="reports/launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = ARCHS[args.arch].tiny() if args.tiny else ARCHS[args.arch]
    if args.shape:
        cell = SHAPE_CELLS[args.shape]
        args.batch, args.seq = cell.global_batch, cell.seq_len
    model = build_model(cfg)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size))
    tcfg = TrainConfig(lr=3e-3, warmup=20, total_steps=args.steps)
    train_step, opt = make_train_step(model, tcfg)

    mesh = build_mesh()
    ctx = axis_rules(mesh) if mesh is not None else _null_ctx()
    with ctx:
        if mesh is not None:
            p_sh = S.param_shardings(mesh, model)
            init = jax.jit(lambda k: model.init(k), out_shardings=p_sh)
            params = init(jax.random.PRNGKey(0))
        else:
            params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        step_fn = jax.jit(train_step)

        start = 0
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            restored = ckpt.restore(
                args.ckpt_dir, last, {"params": params, "opt": opt_state},
                shardings={"params": S.param_shardings(mesh, model),
                           "opt": None} if mesh is not None else None)
            params, opt_state, start = (restored["params"], restored["opt"],
                                        last)
            print(f"resumed from step {last} "
                  f"(mesh {'x'.join(map(str, mesh.devices.shape)) if mesh else 'local'})")

        t0 = time.time()
        metrics = {}
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     data.batch(step, args.batch, args.seq,
                                host=jax.process_index(),
                                n_hosts=jax.process_count()).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % 20 == 0:
                print(f"step {step:5d} loss {float(metrics['loss']):.3f}",
                      flush=True)
            if step and step % args.ckpt_every == 0:
                ckpt.save_async(args.ckpt_dir, step,
                                {"params": params, "opt": opt_state})
        ckpt.wait_pending()
        ckpt.save(args.ckpt_dir, args.steps,
                  {"params": params, "opt": opt_state})
        dt = time.time() - t0
        print(f"done {args.steps - start} steps in {dt:.1f}s; "
              f"final loss {float(metrics.get('loss', float('nan'))):.3f}")


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
