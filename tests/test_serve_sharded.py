"""Tensor-parallel serving (DESIGN.md §13): greedy identity across mesh
shapes, the one-logits-all-gather decode invariant, device-count errors,
and divisibility warnings.

Multi-device tests run in subprocesses (the virtual device count must be
set before jax initializes) so the plain single-device test run stays
valid — same idiom as test_sharding.py.
"""
import logging
import os
import subprocess
import sys

import pytest

from repro.dist.sharding import (DEFAULT_RULES, SERVE_DECODE_RULES,
                                 active_rule, axis_rules, logical_to_spec,
                                 row_parallel)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)


# shared preamble: tiny target, FAQ-packed int4 weights, synthetic prompts
_SETUP = """
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import ARCHS
from repro.core import QuantSpec, quantize_model, run_calibration
from repro.data.synthetic import DataConfig, SyntheticLM, calibration_batches
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine
from repro.launch.mesh import make_local_mesh

def build(cfg):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size))
    calib = calibration_batches(data, 4, 32)
    stats = run_calibration(model.forward, params,
                            [{k: jnp.asarray(v) for k, v in b.items()}
                             for b in calib])
    qp, _ = quantize_model(params, model.quant_site_map(), stats,
                           method="faq",
                           spec=QuantSpec(bits=4, group_size=64),
                           mode="packed")
    return model, qp, stats, data

def reqs(data):
    return [Request(rid=i, prompt=data.sequence(77 + i, 9 + i),
                    max_new_tokens=8) for i in range(3)]
"""


# ---------------------------------------------------------------------------
# Fast single-device tests (run in the plain tier-1 suite)
# ---------------------------------------------------------------------------

def test_mesh_device_count_error():
    """make_local_mesh / make_production_mesh must refuse — naming the
    required vs available counts — instead of silently slicing a too-small
    jax.devices()."""
    import jax

    from repro.launch.mesh import make_local_mesh, make_production_mesh
    avail = len(jax.devices())
    with pytest.raises(ValueError, match=r"requires 16 devices"):
        make_local_mesh(4, 4)        # 16 > both 1 and the CI's 8
    with pytest.raises(ValueError, match=str(avail)):
        make_local_mesh(4, 4)
    with pytest.raises(ValueError, match=r"requires 256 devices"):
        make_production_mesh()


def test_divisibility_warn_once(caplog):
    """A dropped shard axis warns exactly once per unique site."""
    mesh = FakeMesh({"data": 16, "model": 16})
    args = dict(mesh=mesh, rules=DEFAULT_RULES)
    with caplog.at_level(logging.WARNING, logger="repro.dist.sharding"):
        for _ in range(3):   # identical site: one warning total
            logical_to_spec(["batch", None, "kv_heads", None],
                            shape=(256, 4, 10, 128), **args)
        warns = [r for r in caplog.records if "NOT sharded" in r.message]
        assert len(warns) == 1
        assert "kv_heads" in warns[0].message and "10" in warns[0].message
        # a different shape is a different site: warns again
        logical_to_spec(["batch", None, "kv_heads", None],
                        shape=(256, 4, 12, 128), **args)
        warns = [r for r in caplog.records if "NOT sharded" in r.message]
        assert len(warns) == 2
        # singleton dims replicate silently (nothing to lose)
        logical_to_spec(["batch", "kv_heads"], shape=(256, 1), **args)
        warns = [r for r in caplog.records if "NOT sharded" in r.message]
        assert len(warns) == 2


def test_row_parallel_rebinds_qin():
    """row_parallel() disarms the packed-domain constraint exactly in the
    decode regime (qin None -> "model") and is a no-op elsewhere."""
    mesh = FakeMesh({"data": 1, "model": 4})
    with axis_rules(mesh, SERVE_DECODE_RULES):
        assert active_rule("qin") is None
        with row_parallel():
            assert active_rule("qin") == "model"
            assert active_rule("heads") == "model"   # rest of table intact
        assert active_rule("qin") is None
    # default rules: qin already bound, context changes nothing
    with axis_rules(mesh, DEFAULT_RULES):
        with row_parallel():
            assert active_rule("qin") == DEFAULT_RULES["qin"]
    # no active mesh: no-op
    with row_parallel():
        assert active_rule("qin") == DEFAULT_RULES["qin"]


# ---------------------------------------------------------------------------
# Multi-device subprocess tests
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_identity_matrix():
    """Greedy outputs are token-for-token identical to the single-device
    engine for dense and paged serving, with and without speculative
    decoding, at mesh shapes (1,2) and (1,4) — plus the non-dividing
    head-count fallback (KH=2 on model=4, GSPMD path, no shard_map)."""
    code = _SETUP + """
from repro.serve.draft import self_int8_draft
from repro.serve.spec import SpecConfig

cfg = dataclasses.replace(ARCHS["llama3-8b"].tiny(), n_kv_heads=4)
model, qp, stats, data = build(cfg)

def run(**kw):
    sc = (SpecConfig(k=2, draft=self_int8_draft(model, qp, stats))
          if kw.pop("spec", False) else None)
    eng = ServeEngine(model, qp, n_slots=2, max_len=64, spec=sc, **kw)
    return eng.serve(reqs(data))

modes = [{}, {"paged": True}, {"spec": True}, {"paged": True, "spec": True}]
refs = [run(**dict(m)) for m in modes]
for r in refs[0]:
    assert all(refs[0][r].tolist() == ref[r].tolist() for ref in refs[1:])
for shape in [(1, 2), (1, 4)]:
    mesh = make_local_mesh(*shape)
    for m, ref in zip(modes, refs):
        got = run(mesh=mesh, **dict(m))
        for r in ref:
            assert got[r].tolist() == ref[r].tolist(), (shape, m, r)

# head count (KH=2) not dividing model=4: the shard_map guard must skip
# cleanly and GSPMD still reproduce the reference bit-for-bit
cfg2 = ARCHS["llama3-8b"].tiny()
model2, qp2, _, data2 = build(cfg2)
ref = ServeEngine(model2, qp2, n_slots=2, max_len=64).serve(reqs(data2))
got = ServeEngine(model2, qp2, n_slots=2, max_len=64,
                  mesh=make_local_mesh(1, 4)).serve(reqs(data2))
for r in ref:
    assert got[r].tolist() == ref[r].tolist()
print("IDENTITY-OK")
"""
    out = _run(code)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "IDENTITY-OK" in out.stdout


@pytest.mark.slow
def test_decode_collective_invariant():
    """The compiled sharded decode step contains exactly one all-gather
    (the logits) and no KV-cache collectives: zero all-to-all /
    collective-permute, and every all-reduce is activation-sized
    (B * d_model partial sums), never cache-sized.  Also checks the TP
    placement of quantized leaves (codes and scales split on the same
    axis) and that steady-state decode compiles exactly once."""
    code = _SETUP + """
import re
from repro.dist.sharding import SERVE_DECODE_RULES, axis_rules

cfg = ARCHS["llama3-8b"].tiny()        # KH=2 shards on model=2
model, qp, stats, data = build(cfg)
mesh = make_local_mesh(1, 2)
eng = ServeEngine(model, qp, n_slots=2, max_len=64, mesh=mesh)

# quantized TP layout: wq column-parallel — codes and scale both split
# their output dim on "model"; wo row-parallel — codes split the input
# (head) dim instead
wq, wo = eng.params["blocks"]["wq"], eng.params["blocks"]["wo"]
assert wq.codes.sharding.spec[2] == "model", wq.codes.sharding.spec
assert wq.scale.sharding.spec[2] == "model", wq.scale.sharding.spec
assert wo.codes.sharding.spec[1] == "model", wo.codes.sharding.spec
k_shard = eng._place(model.init_cache(2, 64), eng._cache_axes)
assert k_shard["k"].sharding.spec[2] == "model"   # head-sharded KV

args = (eng.params, k_shard, jnp.zeros((2,), jnp.int32),
        jnp.ones((2,), bool), jnp.zeros((2,), jnp.float32), None, None,
        jax.random.PRNGKey(0))
with axis_rules(mesh, SERVE_DECODE_RULES):
    txt = eng._decode.fn.jitted.lower(*args).compile().as_text()

def defs(kind):
    return re.findall(r"= (\\S+) %s\\(" % kind, txt)

assert len(defs("all-gather")) == 1, txt.count("all-gather")
v_pad = eng.params["lm_head"].shape[-1]   # padded vocab (fp16/fp32 head)
(ag_ty,) = defs("all-gather")
assert str(v_pad) in ag_ty            # it IS the logits gather
assert len(defs("all-to-all")) == 0
assert len(defs("collective-permute")) == 0
for ty in defs("all-reduce"):
    dims = [int(d) for d in re.findall(r"\\d+", ty.split("[")[1])]
    n = 1
    for d in dims:
        n *= d
    assert n <= 2 * cfg.d_model, ty   # activation-sized, never KV-sized

# steady-state: the greedy decode step compiles exactly once end to end
out = eng.serve(reqs(data))
assert eng._decode.traces == 1, eng._decode.traces
assert eng._decode.calls > 1
print("INVARIANT-OK")
"""
    out = _run(code)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "INVARIANT-OK" in out.stdout


def test_kernel_quant_matmul_shard_map():
    """Kernel mode on a (1, 4) mesh: the Pallas dequant-matmul runs under
    shard_map (GSPMD cannot partition a Mosaic kernel), column-parallel
    or — inside row_parallel() — split over input channels with an
    all-reduce; both equal the single-device jnp reference."""
    code = """
import os
os.environ["REPRO_KERNEL_MODE"] = "interpret"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import QuantSpec, quantize_groupwise
from repro.dist.sharding import SERVE_DECODE_RULES, axis_rules, row_parallel
from repro.kernels import ref
from repro.kernels.ops import quant_matmul
from repro.launch.mesh import make_local_mesh

mesh = make_local_mesh(1, 4)
k, n = 512, 256
w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
s = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (k,))) + 0.5
x = jax.random.normal(jax.random.PRNGKey(2), (6, k))
qt = quantize_groupwise(w, QuantSpec(bits=4, group_size=64), act_scale=s,
                        pack=True)
expect = np.asarray(ref.quant_matmul_ref(x, qt))
for row in (False, True):
    spec = P("model", None) if row else P(None, "model")
    placed = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(
            mesh, spec if a.ndim == 2 else P())), qt)

    def f(x, qt):
        with axis_rules(mesh, SERVE_DECODE_RULES):
            if row:
                with row_parallel():
                    return quant_matmul(x, qt)
            return quant_matmul(x, qt)

    lowered = jax.jit(f).lower(x, placed)
    assert ("all-reduce" in lowered.compile().as_text()) == row
    got = np.asarray(jax.jit(f)(x, placed))
    np.testing.assert_allclose(got, expect, atol=1e-3, rtol=1e-3)
print("SHARD-MAP-OK")
"""
    out = _run(code)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARD-MAP-OK" in out.stdout
