"""decode_hbm_roofline_pct: least HBM bytes of a decode step (bench.work:
packed int4 block linears, bf16 lm_head, each active slot's live KV)
over the bytes the chip's HBM moves in the step's device time, in
percent.  Steps are those the benchmark dispatched in the traced
window; the device time per step is the trace's."""
from bench import work
from bench.peaks import peaks_for


def read(run):
    p = (run.trace or {}).get("programs", {}).get("decode")
    if not p or not p["count"] or run.traced is None:
        return None
    _, steps = run.steplog.between(*run.traced)
    if not steps:
        return None
    nbytes = sum(work.decode_bytes(run.sizes, ctx) for _, ctx in steps)
    per_step = nbytes / len(steps)
    bw = peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * per_step / (p["seconds"] / p["count"] * bw)
