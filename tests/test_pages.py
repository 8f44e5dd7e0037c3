"""Paged KV cache: pool allocator units, COW, prefix sharing, and
serve() == generate() equivalence on paged fp16 / int8-KV caches."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import QuantSpec, quantize_model, run_calibration
from repro.models.registry import build_model
from repro.serve import PagePool, Request, ServeEngine, block_hashes


# -- pool units --------------------------------------------------------------

def test_pool_alloc_free_refcount():
    pool = PagePool(5, 8)          # trash + 4 allocatable
    a = pool.alloc()
    b = pool.alloc()
    assert a != b and PagePool.TRASH not in (a, b)
    assert pool.pages_in_use() == 2
    pool.incref(a)
    pool.decref(a)
    assert pool.pages_in_use() == 2     # still one owner left
    pool.decref(a)
    assert pool.pages_in_use() == 1     # refcount 0 -> freed
    c = pool.alloc()
    assert pool.pages_in_use() == 2
    pool.decref(b)
    pool.decref(c)
    assert pool.pages_in_use() == 0
    assert pool.in_use_peak == 2


def test_pool_exhaustion_and_eviction():
    pool = PagePool(3, 8)          # 2 allocatable pages
    a = pool.alloc()
    b = pool.alloc()
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc()
    # a page whose only owner is the prefix index is evictable
    pool.register(b"h", a)
    pool.decref(a)                 # slot retires; index keeps it alive
    assert pool.pages_in_use() == 2 and b"h" in pool.index
    c = pool.alloc()               # forces eviction of the index entry
    assert c == a and b"h" not in pool.index
    assert pool.evictions == 1
    pool.decref(b)
    pool.decref(c)


def test_pool_match_walks_prefix_chain():
    pool = PagePool(8, 4)
    toks = np.arange(12)
    hashes = block_hashes(toks, 4)
    assert len(hashes) == 3
    # chained hashes: same block content at a different depth differs
    assert len(set(hashes)) == 3
    p0, p1 = pool.alloc(), pool.alloc()
    pool.register(hashes[0], p0)
    pool.register(hashes[1], p1)
    got = pool.match(hashes)       # third block unregistered -> stop
    assert got == [p0, p1]
    assert pool.ref[p0] == 3 and pool.ref[p1] == 3  # slot+index+match
    # divergent prefix matches nothing past the divergence
    other = block_hashes(np.concatenate([toks[:4], toks[:8]]), 4)
    assert other[0] == hashes[0] and other[1] != hashes[1]
    assert pool.lookup_blocks(other) == 1


def test_block_hashes_full_blocks_only():
    assert len(block_hashes(np.arange(7), 4)) == 1
    assert len(block_hashes(np.arange(3), 4)) == 0
    a = block_hashes(np.arange(8), 4)
    b = block_hashes(np.arange(8), 4)
    assert a == b                  # deterministic across calls


# -- engine integration ------------------------------------------------------

@pytest.fixture(scope="module")
def quantized_setup():
    cfg = ARCHS["llama3-8b"].tiny()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, cfg.vocab_size)}
    stats = run_calibration(m.forward, params, [batch])
    qp, _ = quantize_model(params, m.quant_site_map(), stats, method="faq",
                           spec=QuantSpec(bits=4, group_size=64),
                           mode="packed")
    return cfg, m, qp


def _mixed_shared_requests(cfg, n, prefix_len, seed=0, max_new=(1, 8)):
    rng = np.random.default_rng(seed)
    sysp = rng.integers(0, cfg.vocab_size, size=prefix_len)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [sysp, rng.integers(0, cfg.vocab_size,
                                            size=int(rng.integers(3, 20)))]),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


def test_paged_serve_matches_generate(quantized_setup):
    """Token-for-token: paged mixed-length continuous batching must
    reproduce the single-request dense-cache greedy outputs exactly."""
    cfg, m, qp = quantized_setup
    eng = ServeEngine(m, qp, n_slots=3, max_len=64, paged=True, page_size=8)
    assert eng.paged
    reqs = _mixed_shared_requests(cfg, 6, prefix_len=16, seed=0)
    batched = eng.serve([Request(rid=r.rid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens)
                         for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(batched[r.rid], eng.generate(r))
    mm = eng.metrics()
    assert mm["prefix_hits"] >= 1
    assert mm["pages_peak"] <= mm["pages_total"]


def test_paged_serve_matches_generate_kv8():
    """Same equivalence on the int8 KV cache: scales page alongside
    codes, so the int8 fold survives paging."""
    cfg = dataclasses.replace(ARCHS["llama3-8b"].tiny(), kv_cache_bits=8)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(m, params, n_slots=2, max_len=48, paged=True,
                      page_size=8)
    assert eng.paged and eng._store["k"].dtype == np.int8
    reqs = _mixed_shared_requests(cfg, 4, prefix_len=16, seed=1)
    batched = eng.serve([Request(rid=r.rid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens)
                         for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(batched[r.rid], eng.generate(r))
    assert eng.metrics()["prefix_hits"] >= 1


@pytest.mark.parametrize("window", [0, 12])
def test_attention_page_counters_match_the_steps(window):
    """``serve.attn_pages_live`` adds, for every plain decode step, the
    pages each slot's attention reaches (from the first position its
    window holds to the step's own token), and ``serve.attn_pages_table``
    the table the kernel is handed; checked against the lengths the
    jitted step actually received."""
    cfg = dataclasses.replace(ARCHS["llama3-8b"].tiny(),
                              sliding_window=window)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    ps = 8
    eng = ServeEngine(m, params, n_slots=3, max_len=48, paged=True,
                      page_size=ps)
    seen = []
    decode = eng._stepper._decode_paged

    def spy(*args):
        seen.append((np.asarray(args[3]), args[2].shape))
        return decode(*args)

    eng._stepper._decode_paged = spy
    eng.serve(_mixed_shared_requests(cfg, 5, prefix_len=3, seed=2,
                                     max_new=(4, 12)))
    live = table = 0
    for lens, shape in seen:
        for n in lens + 1:
            lo = max(n - window, 0) if window else 0
            live += len({pos // ps for pos in range(lo, n)})
        table += shape[0] * shape[1]
    reg = eng.registry.snapshot()
    assert len(seen) == reg["serve.decode_steps"] > 0
    assert reg["serve.attn_pages_live"] == live
    assert reg["serve.attn_pages_table"] == table == len(seen) * 3 * 6


def test_prefix_sharing_refcounts_and_skipped_prefill(quantized_setup):
    """Two requests sharing a 2-block prefix must map the same physical
    pages (refcounted: index + both slots) and only the second request's
    tail goes through prefill work."""
    cfg, m, qp = quantized_setup
    ps = 8
    eng = ServeEngine(m, qp, n_slots=2, max_len=64, paged=True, page_size=ps)
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab_size, size=2 * ps)   # 2 full blocks
    pa = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, size=5)])
    pb = np.concatenate([prefix, rng.integers(0, cfg.vocab_size, size=9)])
    hashes = block_hashes(prefix, ps)

    seen_refs = []

    def snapshot(rid, tok):
        # rid 1's first token lands after its fill completes, while
        # rid 0 (bigger budget) is still resident in the other slot
        if rid == 1 and not seen_refs:
            phys = [eng.pool.index.get(h) for h in hashes]
            seen_refs.append([None if p is None else int(eng.pool.ref[p])
                              for p in phys])

    ra = Request(rid=0, prompt=pa, max_new_tokens=15, on_token=snapshot)
    rb = Request(rid=1, prompt=pb, max_new_tokens=6, on_token=snapshot)
    res = eng.serve([ra, rb])
    mm = eng.metrics()
    # the second request's leading 2 blocks came from the index
    assert mm["prefix_hits"] == 1
    assert mm["prefix_hit_tokens"] == 2 * ps
    # while both slots were resident, each shared page had 3 owners:
    # the prefix index plus both slots
    assert seen_refs and seen_refs[0] == [3, 3]
    # after retirement the index keeps one ref per shared block
    for h in hashes:
        assert int(eng.pool.ref[eng.pool.index[h]]) == 1
    for r in (ra, rb):
        np.testing.assert_array_equal(
            res[r.rid],
            eng.generate(Request(rid=r.rid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens)))


def test_cow_on_fully_cached_prompt(quantized_setup):
    """A prompt whose every block is cached re-feeds its last token; the
    write into the shared final page must copy-on-write, never mutate
    the shared block."""
    cfg, m, qp = quantized_setup
    eng = ServeEngine(m, qp, n_slots=2, max_len=32, paged=True, page_size=8)
    prompt = (np.arange(16) % cfg.vocab_size).astype(np.int32)  # 2 pages
    r1 = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=3)])
    r2 = eng.serve([Request(rid=1, prompt=prompt, max_new_tokens=3)])
    mm = eng.metrics()
    assert mm["cow_copies"] == 1
    assert mm["prefix_hit_tokens"] == 15          # n-1 of 16
    np.testing.assert_array_equal(r1[0], r2[1])
    np.testing.assert_array_equal(
        r2[1], eng.generate(Request(rid=9, prompt=prompt, max_new_tokens=3)))


def test_paged_peak_memory_below_dense(quantized_setup):
    """16 mixed-length shared-prefix requests: peak pinned page bytes
    must undercut the dense n_slots*max_len allocation."""
    cfg, m, qp = quantized_setup
    max_len, n_slots = 128, 4
    eng = ServeEngine(m, qp, n_slots=n_slots, max_len=max_len, paged=True,
                      page_size=16)
    reqs = _mixed_shared_requests(cfg, 16, prefix_len=32, seed=5,
                                  max_new=(4, 12))
    eng.serve(reqs)
    dense_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: m.init_cache(n_slots, max_len))))
    mm = eng.metrics()
    assert mm["peak_cache_bytes"] < dense_bytes
    assert mm["prefix_hits"] >= 10


def test_paged_capacity_truncation(quantized_setup):
    """Capacity semantics survive paging: a request that fills its
    max_len cache truncates exactly like the dense engine."""
    cfg, m, qp = quantized_setup
    max_len = 24
    eng = ServeEngine(m, qp, n_slots=2, max_len=max_len, buckets=(8, 24),
                      paged=True, page_size=8)
    prompt = (np.arange(8) % cfg.vocab_size).astype(np.int32)
    res = eng.serve([
        Request(rid=0, prompt=prompt, max_new_tokens=2),
        Request(rid=1, prompt=prompt, max_new_tokens=100),
    ])
    assert res[0].shape == (2,)
    assert res[1].shape == (1 + max_len - len(prompt),)
    assert eng.metrics()["truncated"] == 1
    big = ServeEngine(m, qp, n_slots=2, max_len=64)
    ref = big.generate(Request(rid=9, prompt=prompt, max_new_tokens=100))
    np.testing.assert_array_equal(res[1], ref[:len(res[1])])
    # all transient pages returned; only index-registered blocks persist
    assert eng.pool.pages_in_use() == len(eng.pool.index)


def test_paged_falls_back_for_unsupported_models():
    """hymba's ring-buffer cache can't page; the engine silently serves
    from the dense path."""
    cfg = ARCHS["hymba-1.5b"].tiny()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(m, params, n_slots=2, max_len=48, paged=True)
    assert not eng.paged
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=6),
                    max_new_tokens=2) for i in range(2)]
    res = eng.serve(reqs)
    assert all(res[i].shape == (2,) for i in range(2))
