"""host_gap_ms: host time per decode step during which no step is queued
on the device: the engine's own ``prepare`` (page writability, argument
arrays), ``dispatch`` (the jitted call until it returns) and ``emit``
(per-slot emission and finish checks) phases, summed from the registry's
``serve.step_ms{phase=...}`` histograms over the window and divided by
``serve.decode_steps``.  The engine feeds those histograms only when it
is observed (``profile=True`` in the traced run); without them, as in a
program that has no such phases, the reader returns None."""

PHASES = ("prepare", "dispatch", "emit")


def read(run):
    steps = run.reg.get("serve.decode_steps", 0)
    hists = [run.reg.get(f"serve.step_ms{{phase={p}}}") for p in PHASES]
    if not steps or any(not isinstance(h, dict) for h in hists):
        return None
    return sum(h["sum"] for h in hists) / steps
