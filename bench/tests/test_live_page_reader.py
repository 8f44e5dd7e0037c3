"""The reader of the paged decode's live-page counters, on synthetic run
data and on a traced tiny run: the share of the page table walked, and
None where the counters are missing (a program that does not count).

    python -m pytest bench/tests
"""
import pytest

from bench.tests.tiny import setup_env, tiny_cell

setup_env()


def _read(name, **kw):
    from bench.run import RunData, read_metric

    return read_metric(name, RunData(**kw))


def test_live_page_share_reads_the_counters():
    reg = {"serve.attn_pages_live": 1450, "serve.attn_pages_table": 8192}
    assert _read("decode_live_page_pct", reg=reg) == pytest.approx(
        100 * 1450 / 8192)


@pytest.mark.parametrize("reg", [
    {},                                                # no counters
    {"serve.attn_pages_live": 0, "serve.attn_pages_table": 0},  # no steps
    {"serve.attn_pages_table": 8192},                  # live counter missing
])
def test_live_page_share_none_without_counters(reg):
    assert _read("decode_live_page_pct", reg=reg) is None


def test_traced_tiny_run_reads_the_live_page_share():
    """The harness's traced path on the CPU: the paged engine counts the
    pages its decode attention walks, and the reader finds them."""
    from bench import run

    r = run.execute(tiny_cell("stablelm-12b.offline-batch"), 2**31 + 13,
                    2.0, trace=True, control=False)
    assert r["correct"], r["checks"]
    assert 0 < r["metrics"]["decode_live_page_pct"]["value"] <= 100
