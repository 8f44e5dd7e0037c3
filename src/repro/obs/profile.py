"""Optional ``jax.profiler`` hooks for the serving entry points.

The span tracer (:mod:`.trace`) answers *host-side* timeline questions;
when the question is "what is the device doing inside that span", the
XLA profiler is the right tool.  This module is the thin, always-safe
seam between the two:

* :func:`profile_session` — wrap a serve/bench run in
  ``jax.profiler.trace(logdir)`` (TensorBoard/Perfetto-readable device
  profile).  ``logdir=None`` degrades to a no-op, so call sites never
  branch.
* :func:`annotation` — a named ``TraceAnnotation`` around one jitted
  entry-point call, so prefill/decode/spec dispatches show up as named
  regions inside the device profile.  ``TraceCounter`` applies it when
  its engine was built with ``profile=True``.

Nothing here is on by default: profiling is opt-in per run
(``launch/serve.py --profile-dir``), and the no-op paths add a single
attribute check to the hot loop.
"""
from __future__ import annotations

from contextlib import contextmanager

from jax import profiler as _profiler


@contextmanager
def profile_session(logdir=None):
    """Device-profile the enclosed block into ``logdir`` (no-op when
    ``logdir`` is falsy)."""
    if not logdir:
        yield None
        return
    with _profiler.trace(str(logdir)):
        yield str(logdir)


def annotation(name: str):
    """Named profiler region for one dispatch."""
    return _profiler.TraceAnnotation(name)
