"""The harness's whole path at a tiny size on the CPU (kernels in
interpret mode): set-up, feed, window, metric readers and the reference
check; the control (int3 tables, float8 tokens), a token altered where
the engine emits it and tables packed with the wrong zero points all
come out as not correct; the command itself refuses the CPU.

    python -m pytest bench/tests
"""
import json
import subprocess
import sys

import pytest

from bench.tests.tiny import REPO, setup_env, tiny_cell

setup_env()


@pytest.fixture(scope="module")
def chat_result():
    from bench import run

    return run.execute(tiny_cell(), 2**33 + 17, 3.0, trace=False,
                       control=False)


def test_open_loop_run_is_correct(chat_result):
    r = chat_result
    assert r["correct"], r["checks"]
    assert r["attempted"] == 12 and r["failed"] == 0
    assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["checks"]["compared_tokens"]["value"] >= 10


def test_control_is_not_correct(chat_result):
    """The reference in lower precision in the program's place fails both
    numbers, each far above the program's reading."""
    from bench import run

    c = run.execute(tiny_cell(), 2**33 + 17, 3.0, trace=False, control=True)
    assert not c["correct"]
    for k in ("max_logit_gap", "table_err_vs_rtn"):
        assert c["checks"][k]["value"] > c["checks"][k]["limit"], k
        assert c["checks"][k]["value"] > 3 * chat_result["checks"][k]["value"]


def test_backlog_run_traced():
    from bench import run

    r = run.execute(tiny_cell("stablelm-12b.offline-batch"), 5, 2.0,
                    trace=True, control=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 8
    # device metrics need a chip: on the CPU only the host ones are read
    assert {"quantize_s", "decode_batch_mean"} <= set(r["metrics"])
    assert r["metrics"]["decode_batch_mean"]["value"] > 1


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where the engine produces it fails the check."""
    from bench import run
    from repro.serve.engine import ServeEngine

    emit = ServeEngine._emit

    def altered(self, req, tok):
        emit(self, req, (tok + 1) % self.model.cfg.vocab_size)

    monkeypatch.setattr(ServeEngine, "_emit", altered)
    r = run.execute(tiny_cell(), 3, 2.0, trace=False, control=False)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]


def test_wrong_zero_points_are_not_correct(monkeypatch):
    """Tables packed with every zero point one code off fail the table
    check, though the engine serves them consistently."""
    from bench import run
    from repro.core.quantizer import QuantizedTensor
    from repro.launch import serve

    build = serve.quantize_for_serving

    def shifted(*a, **kw):
        q = build(*a, **kw)
        blocks = dict(q.qparams["blocks"])
        for n, x in blocks.items():
            if isinstance(x, QuantizedTensor):
                blocks[n] = QuantizedTensor(
                    codes=x.codes, scale=x.scale, zero=x.zero + 1,
                    spec=x.spec, n_in=x.n_in, packed=x.packed,
                    act_scale=x.act_scale)
        q.qparams = dict(q.qparams, blocks=blocks)
        return q

    monkeypatch.setattr(serve, "quantize_for_serving", shifted)
    r = run.execute(tiny_cell(), 4, 2.0, trace=False, control=False)
    assert not r["correct"]
    c = r["checks"]["table_err_vs_rtn"]
    assert c["value"] > c["limit"]


def test_reference_weights_are_the_launchers():
    """The reference draws the same fp weights as the program's init."""
    import jax
    import numpy as np

    from bench import reference, serving
    from repro.configs.base import ModelConfig
    from repro.models.registry import build_model

    cfg = ModelConfig(**tiny_cell()["config"]["model"])
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(0))
    fp = reference.FpModel(serving.model_sizes(cfg))
    for l in range(cfg.n_layers):
        mine = fp.layer(l)
        for n in reference.LINEARS:
            np.testing.assert_array_equal(
                np.asarray(mine[n], np.float32),
                np.asarray(params["blocks"][n][l], np.float32))
    ours = fp.logit_params({})
    for n in ("embed", "lm_head"):
        np.testing.assert_array_equal(np.asarray(ours[n], np.float32),
                                      np.asarray(params[n], np.float32))


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"REPRO_KERNEL_MODE": "interpret"}])
def test_command_refuses_without_chip(env):
    import os

    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("REPRO_KERNEL_MODE", None)
    e.update(env)
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload",
                        "stablelm-12b.offline-batch", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=REPO, env=e,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    last = (p.stdout.strip().splitlines() or [""])[-1]
    with pytest.raises(ValueError):
        json.loads(last)
