"""decode_attention_ms: device time of the paged flash-decode kernel
(``pallas_call`` name ``decode_attention``) per decode step, from the
profiler trace.

The breakdown gives each op's own time over the traced window, under its
HLO name (``decode_attention.5``; the ``.N`` suffix is stripped and the
instances summed).  That time includes the clipped decode executions at
the window's edges, which ``programs.decode`` does not count, so the
reader takes the kernel's share of the window's device time and scales
the decode step's device time by it: exact where the window runs decode
steps alone, as the middle of a backlog does.  None where the trace, the
decode executions or the kernel are missing (a program whose kernel has
another name)."""

import re

KERNEL = "decode_attention"


def read(run):
    t = run.trace or {}
    p = t.get("programs", {}).get("decode")
    if not p or not p["count"] or not t.get("busy_s"):
        return None
    own = sum(s for name, s in t.get("device_ops", ())
              if re.sub(r"\.\d+$", "", name) == KERNEL)
    if not own:
        return None
    return 1e3 * (own / t["busy_s"]) * (p["seconds"] / p["count"])
