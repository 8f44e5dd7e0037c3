"""decode_step_ms: device time per execution of the paged-decode
program (jitted ``_decode_paged_fn``; the workload's ``programs`` maps
the label ``decode`` to that name), from the profiler trace."""


def read(run):
    p = (run.trace or {}).get("programs", {}).get("decode")
    if not p or not p["count"]:
        return None
    return 1e3 * p["seconds"] / p["count"]
