"""A cell of the benchmark cut to a size the CPU runs in seconds, for the
self-tests: the harness's own path at the real cell's settings, with the
model, lengths and buckets shrunk."""
from __future__ import annotations

import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def setup_env():
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["REPRO_KERNEL_MODE"] = "interpret"
    for p in (str(REPO), str(REPO / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def tiny_cell(name: str = "stablelm-12b.chat", rate: float = 4.0) -> dict:
    """``name`` is a cell of ``BENCHMARK.json`` or, for the open-loop
    path, the chat cell's files, which no entry names yet."""
    from bench import serving

    if name == "stablelm-12b.chat":
        cell = serving.load_cell("stablelm-12b.offline-batch")
        cell["name"] = name
        cell["entry"] = dict(cell["entry"], name=name, traffic="chat")
        cell["workload"] = serving.load_json(
            serving.BENCH / "workloads" / f"{name}.json")
        cell["mix"] = serving.load_json(serving.BENCH / "traffic"
                                        / "chat.json")
    else:
        cell = serving.load_cell(name)
    cell["config"]["model"].update(n_layers=2, d_model=128, n_heads=4,
                                   n_kv_heads=2, head_dim=32, d_ff=256,
                                   vocab_size=512)
    backlog = cell["mix"]["arrivals"] == "backlog"
    cell["mix"].update(prompt={"median": 24, "sigma": 0.8, "min": 4,
                               "max": 30 if backlog else 60},
                       output={"median": 6, "sigma": 0.5, "min": 2,
                               "max": 12})
    if backlog:
        cell["mix"]["backlog_per_slot"] = 2
    cell["workload"]["engine"].update(n_slots=4, max_len=80, buckets=[16, 32],
                                      n_pages=None)
    if not backlog:
        cell["workload"]["rate_per_s"] = rate
    cell["workload"]["trace_seconds"] = 1
    # the tiny model's own readings set its gap limit: sound runs read
    # about 0.01 and the float8 control about 0.27 at this size
    cell["workload"]["check"].update(sample_tokens=40, sample_requests=6,
                                     min_compared_tokens=10,
                                     max_logit_gap=0.1)
    return cell
