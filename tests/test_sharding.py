"""Sharding rules + distributed-path equivalence (virtual devices)."""
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import DEFAULT_RULES, logical_to_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    # 8 kv heads don't divide 16 -> replicated
    spec = logical_to_spec(["batch", None, "kv_heads", None],
                           shape=(256, 1, 8, 128), mesh=mesh,
                           rules=DEFAULT_RULES)
    assert spec == P(("data",), None, None, None) or spec == P("data", None, None, None)
    # 32 heads divide -> sharded
    spec = logical_to_spec(["batch", None, "heads", None],
                           shape=(256, 1, 32, 128), mesh=mesh,
                           rules=DEFAULT_RULES)
    assert spec[2] == "model"


def test_axis_used_once_priority():
    """kv_heads (earlier dim) wins 'model'; kv_seq then falls back."""
    mesh = FakeMesh({"data": 16, "model": 16})
    spec = logical_to_spec([None, "batch", "kv_heads", "kv_seq", None],
                           shape=(4, 256, 16, 4096, 128), mesh=mesh,
                           rules=DEFAULT_RULES)
    assert spec[2] == "model" and spec[3] is None
    # 5 kv heads -> heads replicated, sequence takes model
    spec = logical_to_spec([None, "batch", "kv_heads", "kv_seq", None],
                           shape=(4, 256, 5, 4096, 128), mesh=mesh,
                           rules=DEFAULT_RULES)
    assert spec[2] is None and spec[3] == "model"


def test_warn_dropped_keyed_on_logical_name(caplog):
    """The warn-once dedupe key includes the logical axis *name*: two
    sites that agree on position, shape and dropped mesh axes but drop
    a different logical axis must both warn (the name is not derivable
    from the other key parts when a caller resolves aliases)."""
    import logging

    from repro.dist.sharding import _warn_dropped

    axes = ["batch", None, "kv_heads", None]
    shape = (257, 3, 11, 129)            # distinctive: module-global set
    with caplog.at_level(logging.WARNING, logger="repro.dist.sharding"):
        _warn_dropped(axes, shape, 2, "kv_heads", ("model",), 16)
        _warn_dropped(axes, shape, 2, "kv_heads", ("model",), 16)  # dup
        _warn_dropped(axes, shape, 2, "kv_seq", ("model",), 16)    # new
    warns = [r for r in caplog.records if "NOT sharded" in r.message]
    assert len(warns) == 2
    assert "kv_heads" in warns[0].message
    assert "kv_seq" in warns[1].message


def test_missing_mesh_axis_dropped():
    mesh = FakeMesh({"data": 16, "model": 16})  # no "pod"
    spec = logical_to_spec(["batch"], shape=(256,), mesh=mesh,
                           rules=DEFAULT_RULES)
    assert spec[0] in ("data", ("data",))


@pytest.mark.slow
def test_moe_shard_map_equals_local():
    """Numerical equivalence of the expert-parallel shard_map path vs the
    single-device path, on 8 virtual CPU devices (subprocess: device count
    must be set before jax initializes)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, dataclasses, functools
from jax.sharding import PartitionSpec as P
from repro.configs import ARCHS
from repro.models.moe import (_moe_body_sharded, moe_ffn_local,
                              padded_experts)

cfg = dataclasses.replace(ARCHS["qwen2-moe-a2.7b"].tiny(),
                          moe_capacity_factor=16.0)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     devices=jax.devices()[:8])
e_pad = padded_experts(cfg.n_experts, 4)
ks = jax.random.split(jax.random.PRNGKey(0), 5)
d, f = cfg.d_model, cfg.d_ff
x = jax.random.normal(ks[0], (4, 8, d))
router = jax.random.normal(ks[1], (d, e_pad)) * 0.1
wg = jax.random.normal(ks[2], (e_pad, d, f)) * 0.05
wu = jax.random.normal(ks[3], (e_pad, d, f)) * 0.05
wd = jax.random.normal(ks[4], (e_pad, f, d)) * 0.05
y_local, _, _ = moe_ffn_local(x, router, wg, wu, wd, cfg)
body = functools.partial(_moe_body_sharded, cfg=cfg, model_axis="model",
                         fsdp_axes=("data",))
fn = jax.shard_map(body, mesh=mesh,
               in_specs=(P("data", None, None), P(None, None),
                         P("model", "data", None), P("model", "data", None),
                         P("model", None, "data")),
               out_specs=(P("data", None, None), P()), check_vma=False)
y_sh, _ = jax.jit(fn)(x, router, wg, wu, wd)
diff = float(jnp.max(jnp.abs(y_sh - y_local)))
assert diff < 1e-5, diff
print("OK", diff)
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
