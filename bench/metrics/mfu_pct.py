"""mfu_pct: model FLOPs of all prefill and decode work in the traced
window (bench.work) over the traced window times the bf16 peak, in
percent.  Per-call FLOPs are the mean over the calls the benchmark
dispatched in the window, times the executions the trace holds."""
from bench import work
from bench.peaks import peaks_for


def read(run):
    t = run.trace
    if not t or run.traced is None:
        return None
    calls, steps = run.steplog.between(*run.traced)
    progs = t.get("programs", {})
    flops = 0.0
    if steps and progs.get("decode"):
        per = sum(work.decode_flops(run.sizes, c) for _, c in steps)
        flops += per / len(steps) * progs["decode"]["count"]
    if calls and progs.get("prefill"):
        per = sum(work.prefill_flops(run.sizes, n)
                  for _, lens, _ in calls for n in lens)
        flops += per / len(calls) * progs["prefill"]["count"]
    if not flops:
        return None
    peak = peaks_for(run.device["kind"])["bf16_flops"]
    return 100.0 * flops / (t["window_s"] * peak)
