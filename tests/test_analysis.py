"""The analysis package analyzed: every rule fires on a known-bad
snippet at the right line, noqa suppresses, the baseline round-trips,
the CLI exit codes hold, and the HLO contract checker rejects a broken
contract (text-level fast; one real lowering under the slow marker)."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import code_line_count, run_lint
from repro.analysis.lint import (apply_baseline, collect_files,
                                 load_baseline, write_baseline)
from repro.analysis.rules import all_rules, rules_by_code

REPO = Path(__file__).resolve().parent.parent


def lint_snippet(tmp_path, rel, text, *codes):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    rules = rules_by_code(*codes) if codes else all_rules()
    return run_lint([str(p)], rules, base=tmp_path)


# ---------------------------------------------------------------------------
# One known-bad snippet per rule, asserting the exact line
# ---------------------------------------------------------------------------

def test_rpr001_raw_jit_in_serve(tmp_path):
    findings = lint_snippet(tmp_path, "repro/serve/x.py", (
        "import jax\n"
        "jf = jax.jit(lambda x: x)\n"), "RPR001")
    assert [(f.rule, f.line) for f in findings] == [("RPR001", 2)]
    # same code outside serve/ is fine (the seam lives elsewhere)
    assert not lint_snippet(tmp_path, "repro/core/x.py", (
        "import jax\n"
        "jf = jax.jit(lambda x: x)\n"), "RPR001")


def test_rpr002_host_sync_in_jitted_body(tmp_path):
    findings = lint_snippet(tmp_path, "repro/core/x.py", (
        "import jax\n"
        "import numpy as np\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.asarray(x)\n"), "RPR002")
    assert [(f.rule, f.line) for f in findings] == [("RPR002", 6)]


def test_rpr002_transitive_and_callsite_rooting(tmp_path):
    # helper() is only jitted transitively, via jax.jit(outer)
    findings = lint_snippet(tmp_path, "repro/core/y.py", (
        "import jax\n"
        "\n"
        "def helper(x):\n"
        "    return x.item()\n"
        "\n"
        "def outer(x):\n"
        "    return helper(x)\n"
        "\n"
        "f = jax.jit(outer)\n"), "RPR002")
    assert [(f.rule, f.line) for f in findings] == [("RPR002", 4)]


def test_rpr002_serve_hot_path_methods(tmp_path):
    # transfer initiators in known per-step serve methods are flagged
    # even outside jit (they run on the host between jitted steps)
    findings = lint_snippet(tmp_path, "repro/serve/eng.py", (
        "import numpy as np\n"
        "\n"
        "class Eng:\n"
        "    def _plain_step(self, st):\n"
        "        return np.asarray(st.slot_last)\n"), "RPR002")
    assert [(f.rule, f.line) for f in findings] == [("RPR002", 5)]


def test_rpr003_scalar_args_without_static(tmp_path):
    findings = lint_snippet(tmp_path, "repro/core/z.py", (
        "import jax\n"
        "\n"
        "def f(x, k: int):\n"
        "    return x\n"
        "\n"
        "g = jax.jit(f)\n"), "RPR003")
    assert [(f.rule, f.line) for f in findings] == [("RPR003", 6)]
    assert "'k'" in findings[0].message or "k" in findings[0].message
    # declaring it static clears the finding
    assert not lint_snippet(tmp_path, "repro/core/z2.py", (
        "import jax\n"
        "\n"
        "def f(x, k: int):\n"
        "    return x\n"
        "\n"
        "g = jax.jit(f, static_argnames=('k',))\n"), "RPR003")


def test_rpr004_kernel_accum_dtype(tmp_path):
    findings = lint_snippet(tmp_path, "repro/kernels/k.py", (
        "import jax.numpy as jnp\n"
        "\n"
        "def _kernel(a, b):\n"
        "    s = jnp.cumsum(a)\n"
        "    return jnp.dot(a, b)\n"), "RPR004")
    assert [(f.rule, f.line) for f in findings] == [("RPR004", 4),
                                                    ("RPR004", 5)]
    assert not lint_snippet(tmp_path, "repro/kernels/k2.py", (
        "import jax.numpy as jnp\n"
        "\n"
        "def _kernel(a, b):\n"
        "    s = jnp.cumsum(a, dtype=jnp.float32)\n"
        "    return jnp.dot(a, b, preferred_element_type=jnp.float32)\n"),
        "RPR004")


def test_rpr005_serve_loop_regrowth(tmp_path):
    findings = lint_snippet(tmp_path, "repro/serve/engine.py", (
        "class ServeEngine:\n"
        "    def serve(self):\n"
        "        if self.paged:\n"
        "            return self._stepper.step()\n"
        "        self._stepper.begin()\n"
        "\n"
        "def _serve_paged(eng):\n"
        "    pass\n"), "RPR005")
    assert [(f.rule, f.line) for f in findings] == [
        ("RPR005", 3),   # self.paged branching in the loop
        ("RPR005", 4),   # stepper internals beyond begin()
        ("RPR005", 7),   # second serve loop
    ]


def test_rpr006_clock_seam(tmp_path):
    findings = lint_snippet(tmp_path, "repro/serve/sched.py", (
        "import time\n"
        "\n"
        "def now(clock=None):\n"
        "    return (clock or time.monotonic)()\n"), "RPR006")
    assert [(f.rule, f.line) for f in findings] == [("RPR006", 4)]
    # time.sleep is not a clock read
    assert not lint_snippet(tmp_path, "repro/serve/sched2.py", (
        "import time\n"
        "time.sleep(0)\n"), "RPR006")


def test_rpr007_bare_tile_assert(tmp_path):
    findings = lint_snippet(tmp_path, "repro/kernels/q.py", (
        "def f(k, bk):\n"
        "    assert k % bk == 0\n"), "RPR007")
    assert [(f.rule, f.line) for f in findings] == [("RPR007", 2)]


def test_rpr010_unnamed_pallas_call(tmp_path):
    findings = lint_snippet(tmp_path, "repro/kernels/k.py", (
        "from jax.experimental import pallas as pl\n"
        "\n"
        "def f(kernel, shape, x):\n"
        "    return pl.pallas_call(kernel, out_shape=shape)(x)\n"), "RPR010")
    assert [(f.rule, f.line) for f in findings] == [("RPR010", 4)]
    # a name, or a **kwargs that may carry one, passes
    assert not lint_snippet(tmp_path, "repro/kernels/k2.py", (
        "from jax.experimental import pallas as pl\n"
        "\n"
        "def f(kernel, shape, x, **kw):\n"
        "    a = pl.pallas_call(kernel, out_shape=shape,\n"
        "                       name='dequant_matmul')(x)\n"
        "    return pl.pallas_call(kernel, out_shape=shape, **kw)(a)\n"),
        "RPR010")
    # kernels/ scope only
    assert not lint_snippet(tmp_path, "repro/serve/k.py", (
        "def f(pl, kernel, shape, x):\n"
        "    return pl.pallas_call(kernel, out_shape=shape)(x)\n"),
        "RPR010")


def test_rpr010_kernels_tree_is_clean():
    """Every Pallas kernel in the tree states its role by name."""
    kernels = REPO / "src" / "repro" / "kernels"
    assert run_lint([str(kernels)], rules_by_code("RPR010"),
                    base=REPO) == []


def test_rpr008_pool_raise_in_serve(tmp_path):
    findings = lint_snippet(tmp_path, "repro/serve/stepper.py", (
        "from .pages import PoolExhausted\n"
        "\n"
        "def take_page(pool):\n"
        "    p = pool.try_alloc()\n"
        "    if p is None:\n"
        "        raise PoolExhausted('no pages')\n"
        "    if p < 0:\n"
        "        raise RuntimeError('page pool exhausted')\n"), "RPR008")
    assert [(f.rule, f.line) for f in findings] == [("RPR008", 6),
                                                    ("RPR008", 8)]
    # unrelated RuntimeErrors and code outside serve/ are fine
    assert not lint_snippet(tmp_path, "repro/serve/ok.py", (
        "def f(x):\n"
        "    raise RuntimeError('bad dtype')\n"), "RPR008")
    assert not lint_snippet(tmp_path, "repro/core/pool.py", (
        "def f():\n"
        "    raise RuntimeError('pool exhausted')\n"), "RPR008")


def test_rpr008_alloc_terminal_path_is_unreachable_from_serve():
    """The one serve-tree PoolExhausted raise is PagePool.alloc's
    documented terminal path (noqa'd); the serve steppers allocate via
    try_alloc, so the whole serve/ package lints clean under RPR008."""
    serve_dir = REPO / "src" / "repro" / "serve"
    findings = run_lint([str(serve_dir)], rules_by_code("RPR008"),
                        base=REPO)
    assert findings == []
    text = (serve_dir / "pages.py").read_text()
    assert "noqa[RPR008]" in text


def test_rpr009_obs_bypass_in_serve(tmp_path):
    findings = lint_snippet(tmp_path, "repro/serve/x.py", (
        "import logging\n"
        "from datetime import datetime\n"
        "\n"
        "def step(eng):\n"
        "    print('decoded')\n"
        "    t = datetime.now()\n"), "RPR009")
    assert [(f.rule, f.line) for f in findings] == [("RPR009", 1),
                                                    ("RPR009", 5),
                                                    ("RPR009", 6)]
    # printing is the launch scripts' and benches' job — out of scope
    assert not lint_snippet(tmp_path, "repro/launch/x.py", (
        "print('tok/s')\n"), "RPR009")
    # a reasoned noqa keeps a deliberate exception
    assert not lint_snippet(tmp_path, "repro/serve/ok.py", (
        "def dump(eng):\n"
        "    print(eng)  # repro: noqa[RPR009] debug REPL helper\n"),
        "RPR009")


def test_rpr009_serve_tree_is_clean():
    """The serving stack routes all telemetry through repro.obs /
    serve.instrument — no prints, logging, or raw timestamps."""
    serve_dir = REPO / "src" / "repro" / "serve"
    assert run_lint([str(serve_dir)], rules_by_code("RPR009"),
                    base=REPO) == []


# ---------------------------------------------------------------------------
# Suppression + baseline mechanics
# ---------------------------------------------------------------------------

def test_noqa_suppresses_only_named_rule(tmp_path):
    assert not lint_snippet(tmp_path, "repro/kernels/q.py", (
        "def f(k, bk):\n"
        "    assert k % bk == 0  # repro: noqa[RPR007] forced above\n"),
        "RPR007")
    # a noqa for a different code does not suppress
    findings = lint_snippet(tmp_path, "repro/kernels/q2.py", (
        "def f(k, bk):\n"
        "    assert k % bk == 0  # repro: noqa[RPR001] wrong code\n"),
        "RPR007")
    assert len(findings) == 1


def test_baseline_round_trip_and_stale(tmp_path):
    bad = tmp_path / "repro/kernels/q.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f(k, bk):\n    assert k % bk == 0\n")
    rules = rules_by_code("RPR007")
    files = collect_files([str(tmp_path)], base=tmp_path)
    findings = run_lint([], rules, files=files)
    assert findings

    bl_path = tmp_path / "baseline.json"
    write_baseline(bl_path, findings, files)
    baseline = load_baseline(bl_path)
    new, old, stale = apply_baseline(findings, files, baseline)
    assert not new and len(old) == len(findings) and not stale

    # an unrelated edit ABOVE the finding must not churn the baseline
    # (keyed on line text, not line number)
    bad.write_text("import math\n\n\ndef f(k, bk):\n"
                   "    assert k % bk == 0\n")
    files = collect_files([str(tmp_path)], base=tmp_path)
    findings = run_lint([], rules, files=files)
    new, old, stale = apply_baseline(findings, files, baseline)
    assert not new and len(old) == 1 and not stale

    # fixing the finding leaves a stale entry — the baseline can shrink
    bad.write_text("def f(k, bk):\n    return k // bk\n")
    files = collect_files([str(tmp_path)], base=tmp_path)
    findings = run_lint([], rules, files=files)
    new, old, stale = apply_baseline(findings, files, baseline)
    assert not new and not old and len(stale) == 1


def test_code_line_count_insensitive_to_comments():
    base = "def f(x):\n    y = x + 1\n    return y\n"
    noisy = ('"""Module doc.\n\nspanning lines\n"""\n'
             "# a comment\n\n"
             "def f(x):\n"
             '    """docstring"""\n'
             "    # inline note\n"
             "    y = x + 1\n\n"
             "    return y  # trailing\n")
    assert code_line_count(base) == 3
    assert code_line_count(noisy) == 3


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cli(args, cwd):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    return subprocess.run([sys.executable, "-m", "repro.analysis", *args],
                          cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_cli_repo_is_clean():
    out = _cli([], cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "lint clean" in out.stdout


def test_cli_exit_codes_and_baseline_flow(tmp_path):
    bad = tmp_path / "repro/serve/x.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax\njf = jax.jit(lambda x: x)\n")

    out = _cli(["repro"], cwd=tmp_path)
    assert out.returncode == 1
    assert "RPR001" in out.stdout

    out = _cli(["repro", "--write-baseline", "--baseline", "bl.json"],
               cwd=tmp_path)
    assert out.returncode == 0
    assert json.loads((tmp_path / "bl.json").read_text())["findings"]

    out = _cli(["repro", "--baseline", "bl.json"], cwd=tmp_path)
    assert out.returncode == 0
    assert "baselined" in out.stdout

    # --no-baseline reports everything again
    out = _cli(["repro", "--baseline", "bl.json", "--no-baseline"],
               cwd=tmp_path)
    assert out.returncode == 1

    out = _cli(["no/such/dir"], cwd=tmp_path)
    assert out.returncode == 2


# ---------------------------------------------------------------------------
# HLO contract checking (text-level fast; real lowering under slow)
# ---------------------------------------------------------------------------

def test_hlo_check_module_counts_and_sizes():
    from repro.analysis import hlo_audit

    txt = ("  x = f32[2,1,512]{2,1,0} all-gather(y), dims={2}\n"
           "  r = f32[2,64]{1,0} all-reduce(z)\n")
    c = hlo_audit.CONTRACTS[0]          # decode/dense
    assert c.op == "decode" and not c.paged
    # the layout suffix {1,0} must not zero the element product (the
    # bug that made the old inline ceiling check vacuous)
    assert hlo_audit.type_elems("f32[2,64]{1,0}") == 128
    assert hlo_audit.type_elems("f32[]") == 1
    assert not hlo_audit.check_module(txt, c, d_model=128, vocab_pad=512)

    # a vocab-free gather breaks the logits-gather requirement
    bad = txt.replace("f32[2,1,512]{2,1,0}", "f32[2,1,64]{2,1,0}")
    vios = hlo_audit.check_module(bad, c, d_model=128, vocab_pad=512)
    assert any("vocab" in v.message for v in vios)

    # an oversized all-reduce operand trips the elem ceiling
    big = txt.replace("f32[2,64]{1,0}", "f32[2,512]{1,0}")
    vios = hlo_audit.check_module(big, c, d_model=128, vocab_pad=512)
    assert any(v.kind == "all-reduce" and "ceiling" in v.message
               for v in vios)

    # forbidden kinds default to max_count=0
    a2a = txt + "  t = f32[2,64]{1,0} all-to-all(w)\n"
    vios = hlo_audit.check_module(a2a, c, d_model=128, vocab_pad=512)
    assert any(v.kind == "all-to-all" for v in vios)

    # host transfers are violations regardless of collective budgets
    host = txt + "  send(q), is_host_transfer=true\n"
    vios = hlo_audit.check_module(host, c, d_model=128, vocab_pad=512)
    assert any(v.kind == "host-transfer" for v in vios)


def test_broken_contract_table_fails():
    """A deliberately broken table entry must produce violations from
    check_module — the auditor reads the table, not inline constants."""
    from repro.analysis import hlo_audit

    txt = "  x = f32[2,1,512]{2,1,0} all-gather(y)\n"
    broken = dataclasses.replace(
        hlo_audit.CONTRACTS[0], name="decode/dense/broken",
        bounds={"all-gather": hlo_audit.Bound(max_count=0)})
    vios = hlo_audit.check_module(txt, broken, d_model=128, vocab_pad=512)
    assert [v.kind for v in vios] == ["all-gather"]
    assert "allows 0" in vios[0].message


@pytest.mark.slow
def test_hlo_audit_real_lowering_mesh_1x2():
    """The full matrix audits clean at mesh (1, 2) — one all-gather per
    decode step for dense AND paged, spec on — and a broken contract
    row fails against the same lowered HLO (subprocess: the virtual
    device count must be set before jax initializes)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
from repro.analysis import hlo_audit

broken = dataclasses.replace(
    hlo_audit.CONTRACTS[0], name="decode/dense/broken",
    bounds={"all-gather": hlo_audit.Bound(max_count=0)})
vios = hlo_audit.audit(mesh_shape=(1, 2),
                       contracts=hlo_audit.CONTRACTS + (broken,))
real = [v for v in vios if v.contract != "decode/dense/broken"]
fake = [v for v in vios if v.contract == "decode/dense/broken"]
assert not real, [v.render() for v in real]
assert fake, "broken contract produced no violations"
assert any(v.kind == "all-gather" for v in fake)
print("HLO-AUDIT-OK")
"""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "HLO-AUDIT-OK" in out.stdout
