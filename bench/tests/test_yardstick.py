"""Self-checks of the yardstick: work counters against hand sums at the
two configurations, the traffic generator, the trace reduction and the
latency tails.

    python -m pytest bench/tests
"""
import json

import numpy as np
import pytest

from bench import traffic, trace, work
from bench.tests.tiny import REPO, setup_env

setup_env()


def sizes(name):
    m = json.load(open(REPO / "bench" / "configs" / f"{name}.json"))["model"]
    return dict(n_layers=m["n_layers"], d_model=m["d_model"],
                n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                head_dim=m["head_dim"], d_ff=m["d_ff"],
                vocab_size=m["vocab_size"])


# per-layer block linears, summed by hand from the published widths
HAND = {
    # wq 5120x5120, wk/wv 5120x1280, wo 5120x5120, 3 x 5120x13824
    "stablelm-12b": (10, 26214400 * 2 + 6553600 * 2 + 70778880 * 3),
    # wq/wo 7168x7168, wk/wv 7168x1024, 3 x 7168x19200
    "deepseek-coder-33b": (8, 51380224 * 2 + 7340032 * 2 + 137625600 * 3),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_block_params_and_flops(name):
    m = sizes(name)
    layers, per_layer = HAND[name]
    assert work.block_params(m) == layers * per_layer
    n = 1000
    attn = 4 * layers * m["n_heads"] * m["head_dim"] * n * (n + 1) / 2
    head = 2 * m["d_model"] * m["vocab_size"]
    assert work.prefill_flops(m, n) == pytest.approx(
        2 * layers * per_layer * n + attn + head)
    # two slots at contexts 10 and 20: 11 + 21 (query, key) pairs
    dec = 2 * (2 * layers * per_layer + head) \
        + 4 * layers * m["n_heads"] * m["head_dim"] * 32
    assert work.decode_flops(m, [10, 20]) == pytest.approx(dec)


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_bytes(name):
    m = sizes(name)
    layers, per_layer = HAND[name]
    d, v = m["d_model"], work.padded_vocab(m)
    lin = sum(a * b * 0.5 + 2 * (a // 64) * b * 4 + a * 4
              for a, b in work._linears(m))
    assert lin == pytest.approx(per_layer * (0.5 + 8 / 64)
                                + 4 * sum(a for a, _ in work._linears(m)))
    kv = 2 * layers * m["n_kv_heads"] * m["head_dim"] * 2
    want = layers * lin + (2 * layers + 1) * d * 2 + d * v * 2 \
        + kv * (101 + 1) + 2 * d * 2
    assert work.decode_bytes(m, [100, 0]) == pytest.approx(want)


MIX = {"arrivals": "poisson",
       "prompt": {"median": 1024, "sigma": 0.8, "min": 32, "max": 4096},
       "output": {"median": 160, "sigma": 0.7, "min": 8, "max": 512}}


def test_traffic_seeded_and_clipped():
    a = traffic.plan(MIX, rate=2.0, seconds=30, seed=2**33 + 5, vocab=1000,
                     n_slots=8)
    b = traffic.plan(MIX, rate=2.0, seconds=30, seed=2**33 + 5, vocab=1000,
                     n_slots=8)
    c = traffic.plan(MIX, rate=2.0, seconds=30, seed=7, vocab=1000,
                     n_slots=8)
    assert len(a) == 60
    assert all(x.due == y.due and np.array_equal(x.prompt, y.prompt)
               and x.max_new == y.max_new for x, y in zip(a, b))
    # another seed: the same sizes and gaps, in another order
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    assert [x.due for x in a] != [x.due for x in c]
    assert sorted(np.diff([x.due for x in a])) == pytest.approx(
        sorted(np.diff([x.due for x in c])))
    lens = [len(x.prompt) for x in a]
    assert min(lens) >= 32 and max(lens) <= 4096
    assert all(0 <= x.due < 30 for x in a)
    assert all(1 <= x.prompt.min() and x.prompt.max() < 1000 for x in a)


def test_backlog_longest_first_is_the_same_work_every_seed():
    """A backlog queued longest first: every seed queues the same prompt
    lengths in the same order, so the slots open on the same prompts;
    the seed pairs the output lengths and draws the token ids."""
    mix = json.loads((REPO / "bench" / "traffic"
                      / "offline-batch.json").read_text())
    a, b = (traffic.plan(mix, rate=None, seconds=51, seed=s, vocab=1000,
                         n_slots=32) for s in (2**33 + 1, 12345))
    assert len(a) == len(b) == 96
    lens = [len(x.prompt) for x in a]
    assert lens == [len(x.prompt) for x in b] == sorted(lens, reverse=True)
    assert [x.max_new for x in a] != [x.max_new for x in b]
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert all(x.due == 0.0 for x in a + b)


def test_backlog_request_cut_by_the_window_is_late_not_failed():
    from bench import run, serving

    end = 100.0
    cut = serving.Rec(rid=0, plen=900, max_new=512, in_window=True,
                      admit=1.0, done=end + 0.3)
    queued = serving.Rec(rid=1, plen=90, max_new=512, in_window=True,
                         done=end + 0.4)
    dropped = serving.Rec(rid=2, plen=90, max_new=512, in_window=True,
                          admit=1.0, tokens=[5], done=50.0)
    assert not run._failed(cut, True, end)
    assert not run._failed(queued, True, end)
    assert run._failed(dropped, True, end)
    # open loop: every request runs to its end
    assert run._failed(cut, False, end)


def test_lognormal_quantiles_clip():
    xs = traffic.lognormal_lengths({"median": 100, "sigma": 3.0, "min": 10,
                                    "max": 200}, 101)
    assert xs.min() == 10 and xs.max() == 200 and xs[50] == 100


def test_feed_releases_in_due_order():
    items = [traffic.Item(i, d, np.ones(3, np.int32), 2, True)
             for i, d in enumerate([0.5, 0.0, 1.0])]

    class R:
        def __init__(self, it):
            self.rid, self.arrival = it.rid, None

    f = traffic.Feed(items, R)
    f.start(100.0)
    assert [r.rid for r in f.poll(100.6)] == [1, 0]
    assert f.next_time() == 101.0 and f.pending()
    f.close()
    assert not f.pending() and f.poll(200.0) == []


def test_trace_reduction():
    """Busy union, idle share, per-program time and gap names on a small
    recorded trace of two devices."""
    planes = {
        "/host:CPU": {"main": [("bench.traced", 0.0, 10.0),
                               ("bench.decode_step", 4.0, 6.0)]},
        "/device:TPU:0": {
            # the TPU trace names an op by its whole HLO text, and a
            # loop holds its body's ops on the same line
            "XLA Ops": [("%while.1 = (s32[]) while(s32[] %p.0)", 1.0, 4.0),
                        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.1)",
                         1.0, 3.0), ("fusion.2", 3.0, 4.0),
                        ("copy", 7.0, 9.0), ("early", -2.0, 0.5)],
            "XLA Modules": [("jit__decode_paged_fn(1)", 1.0, 4.0),
                            ("jit__prefill_paged_fn(2)", 7.0, 9.0),
                            ("jit__decode_paged_fn(1)", -2.0, 0.5)]},
        "/device:TPU:1": {
            "XLA Ops": [("fusion.1", 0.0, 10.0)],
            "XLA Modules": [("jit__decode_paged_fn(1)", 0.0, 10.0)]},
    }
    r = trace.reduce(planes, {"decode": "_decode_paged_fn",
                              "prefill": "_prefill_paged_fn"},
                     ("bench.decode_step",))
    assert r["window_s"] == 10.0
    # device 0 busy [0, 0.5] + [1, 4] + [7, 9] = 5.5 s; device 1 10 s
    assert r["busy_s"] == pytest.approx((5.5 + 10.0) / 2)
    assert r["programs"]["decode"] == {"count": 1.0, "seconds": 6.5}
    assert r["programs"]["prefill"]["seconds"] == pytest.approx(1.0)
    assert r["idle_gaps"][0] == ["bench.decode_step", 3.0]
    assert ["host_loop", 1.0] in r["idle_gaps"]
    ops = dict(r["device_ops"])
    assert r["device_ops"][0][0] == "fusion.1"
    assert ops["fusion.1"] == pytest.approx((2.0 + 10.0) / 2)
    assert ops["while.1"] == pytest.approx(0.0)      # its own time only
    assert ops["early"] == pytest.approx(0.5 / 2)    # clipped to the window


def test_trace_reads_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.load(str(tmp_path))
    assert planes and any(trace.WINDOW_SPAN == ev[0]
                          for lines in planes.values()
                          for evs in lines.values() for ev in evs)


class _Rec:
    def __init__(self, due, times, admit=None):
        self.due, self.times, self.admit = due, times, admit


def _read(name, recs):
    from bench.run import RunData, read_metric

    return read_metric(name, RunData(recs=recs))


def test_tails_move_with_a_stall():
    steady = [_Rec(i, [i + 0.1 + 0.02 * k for k in range(50)], i + 0.05)
              for i in range(40)]
    stalled = [_Rec(r.due, list(r.times), r.admit) for r in steady]
    for r in stalled[-4:]:                # a 2 s stall hits 4 requests
        r.times = [t + (2.0 if k >= 10 else 0.0)
                   for k, t in enumerate(r.times)]
    assert _read("itl_p95_ms", steady) == pytest.approx(20.0)
    # 4 of 1960 gaps stall: under the 95th percentile, the ITL tail stays
    assert _read("itl_p95_ms", stalled) == pytest.approx(20.0)
    many = [_Rec(r.due, list(r.times)) for r in steady]
    for r in many:                        # a stall every 10th token
        r.times = [t + 0.5 * (k // 10) for k, t in enumerate(r.times)]
    assert _read("itl_p95_ms", many) > 500.0
