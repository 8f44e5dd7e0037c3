"""RPR010: ``pallas_call`` in ``kernels/`` without ``name=``.

A device trace names each Pallas kernel after its ``pallas_call``'s
``name``, or, without one, after whatever scope encloses it: the
dequant-matmul and the prefill flash-attention both read ``_kernel``,
and no per-kernel metric can tell them apart.  Every kernel states its
role (``decode_attention``, ``dequant_matmul``, ...), so that the name
and the metrics that read it survive a rewrite of the kernel.
"""
from __future__ import annotations

import ast
from typing import List

from ..lint import Finding, Rule, SourceFile, last_seg


class UnnamedPallasCall(Rule):
    code = "RPR010"
    title = "pallas_call in kernels/ without name="
    scope = ("repro/kernels/",)

    def check(self, sf: SourceFile) -> List[Finding]:
        out = []
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and last_seg(node.func) == "pallas_call"):
                continue
            # a **kwargs expansion may carry the name
            if any(kw.arg in ("name", None) for kw in node.keywords):
                continue
            out.append(self.finding(
                sf, node,
                "pallas_call without name= shows in a device trace under "
                "its enclosing scope's name — give it the kernel's role "
                "(decode_attention, dequant_matmul, ...)"))
        return out
