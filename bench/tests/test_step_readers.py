"""The readers of the engine's own step phases and named kernels, on
synthetic run data: what they compute, and None where what they read is
missing (an untraced run, or a program without the phases or the kernel
name).

    python -m pytest bench/tests
"""
import pytest

from bench.tests.tiny import setup_env, tiny_cell

setup_env()


def _read(name, **kw):
    from bench.run import RunData, read_metric

    return read_metric(name, RunData(**kw))


def _hist(total_ms, count):
    return {"count": count, "sum": total_ms, "counts": [count],
            "edges": []}


def _reg(steps=20, **sums):
    reg = {"serve.decode_steps": steps}
    for phase, ms in sums.items():
        reg[f"serve.step_ms{{phase={phase}}}"] = _hist(ms, steps)
    return reg


def test_host_gap_sums_the_host_phases_per_step():
    reg = _reg(prepare=40.0, dispatch=30.0, emit=10.0, sampler_sync=4000.0,
               decode_step=4100.0)
    # sampler_sync waits on the device and decode_step encloses it: both
    # stay out of the host gap
    assert _read("host_gap_ms", reg=reg) == pytest.approx(80.0 / 20)


@pytest.mark.parametrize("reg", [
    {},                                                # no steps
    _reg(steps=0, prepare=1.0, dispatch=1.0, emit=1.0),
    _reg(decode_step=4100.0, sampler_sync=4000.0),     # no phase histograms
    _reg(prepare=40.0, dispatch=30.0),                 # emit missing
])
def test_host_gap_none_without_phases_or_steps(reg):
    assert _read("host_gap_ms", reg=reg) is None


def _trace(ops, busy_s=3.9, count=16, seconds=3.84):
    return {"window_s": 4.0, "busy_s": busy_s,
            "programs": {"decode": {"count": count, "seconds": seconds}},
            "device_ops": ops, "idle_gaps": []}


def test_decode_attention_scales_the_step_by_the_kernel_share():
    # two instances of the kernel (suffixes stripped and summed) hold
    # 2.6 s of 3.9 s of device time; a step takes 240 ms on the device
    ops = [["decode_attention.5", 2.0], ["copy.565", 0.5],
           ["decode_attention.12", 0.6], ["dequant_matmul.3", 0.3],
           ["decode_attention_extra.1", 0.1]]
    got = _read("decode_attention_ms", trace=_trace(ops))
    assert got == pytest.approx(1e3 * (2.6 / 3.9) * 0.240)
    # the clipped executions at the window's edges do not inflate it:
    # device time per complete step is what it scales
    assert got < 1e3 * 2.6 / 16


@pytest.mark.parametrize("trace", [
    None,                                              # untraced run
    _trace([["decode_attention.5", 2.0]], count=0, seconds=0.0),
    _trace([["flash_decode_paged_pallas.5", 2.0]]),    # unnamed kernel
    _trace([["decode_attention.5", 2.0]], busy_s=0.0),
])
def test_decode_attention_none_without_trace_steps_or_name(trace):
    assert _read("decode_attention_ms", trace=trace) is None


def test_traced_tiny_run_reads_the_host_gap():
    """The harness's traced path on the CPU: the engine runs with
    ``profile=True``, so its step phases feed the histograms the reader
    reads; the device metrics stay unread without a chip."""
    from bench import run

    r = run.execute(tiny_cell("stablelm-12b.offline-batch"), 2**31 + 11,
                    2.0, trace=True, control=False)
    assert r["correct"], r["checks"]
    assert r["metrics"]["host_gap_ms"]["value"] > 0
    assert "decode_attention_ms" not in r["metrics"]
