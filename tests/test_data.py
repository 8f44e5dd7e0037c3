"""Synthetic data pipeline: determinism, sharding, resume, bias knob."""
import numpy as np

from repro.data.synthetic import (DataConfig, SyntheticLM,
                                  calibration_batches)
from repro.dist.elastic import plan_mesh, resume_batch_indices


def test_deterministic_by_index():
    d1 = SyntheticLM(DataConfig(seed=7))
    d2 = SyntheticLM(DataConfig(seed=7))
    np.testing.assert_array_equal(d1.sequence(42, 64), d2.sequence(42, 64))
    assert not np.array_equal(d1.sequence(42, 64), d1.sequence(43, 64))


def test_host_shards_disjoint_and_complete():
    d = SyntheticLM(DataConfig())
    b0 = d.batch(step=3, batch_size=4, length=8, host=0, n_hosts=2)
    b1 = d.batch(step=3, batch_size=4, length=8, host=1, n_hosts=2)
    all_rows = np.concatenate([b0["tokens"], b1["tokens"]])
    # global single-host batch of 8 covers the same indices
    bg = d.batch(step=3, batch_size=8, length=8, host=0, n_hosts=1)
    assert sorted(map(tuple, all_rows)) == sorted(map(tuple, bg["tokens"]))


def test_resume_indices_match_pipeline():
    idx = resume_batch_indices(step=5, batch_per_host=4, host=1, n_hosts=2)
    assert idx == (41, 43, 45, 47)


def test_bias_knob_changes_distribution():
    d = SyntheticLM(DataConfig())
    fair = calibration_batches(d, 8, 32, biased=False)
    biased = calibration_batches(d, 8, 32, biased=True)
    first_fair = np.concatenate([b["tokens"][:, 0] for b in fair])
    first_biased = np.concatenate([b["tokens"][:, 0] for b in biased])
    assert first_biased.max() < d.cfg.vocab_size // 32
    assert first_fair.max() > first_biased.max()


def test_learnable_structure():
    """The bigram process must be far from uniform (else PPL benchmarks
    are meaningless)."""
    d = SyntheticLM(DataConfig(vocab_size=512))
    assert d.perplexity_upper_bound() < 64  # uniform would be 512


def _dense_trans_cum(cfg):
    """The original dense (v, v) construction, kept as the oracle for
    the on-demand rows."""
    rng = np.random.Generator(np.random.PCG64(cfg.seed))
    v = cfg.vocab_size
    prior = 1.0 / np.arange(1, v + 1) ** cfg.zipf_a
    prior /= prior.sum()
    succ = rng.integers(0, v, size=(v, cfg.branching))
    w = rng.dirichlet(np.ones(cfg.branching) * 0.5, size=v)
    trans = np.zeros((v, v), np.float64)
    rows = np.repeat(np.arange(v), cfg.branching)
    trans[rows, succ.reshape(-1)] += w.reshape(-1)
    trans += 1e-3 * prior[None, :]
    trans /= trans.sum(axis=1, keepdims=True)
    return np.cumsum(trans, axis=1)


def test_on_demand_rows_match_dense_construction():
    """Rows built on demand are bit-identical to the dense matrix, and
    the streams at v=512 are pinned to the dense construction's."""
    cfg = DataConfig(vocab_size=512)
    d = SyntheticLM(cfg)
    dense = _dense_trans_cum(cfg)
    for tok in (0, 1, 255, 511):
        np.testing.assert_array_equal(d.cum_row(tok), dense[tok])
    assert d.sequence(0, 12).tolist() == [
        1, 21, 353, 300, 123, 132, 147, 374, 35, 318, 238, 178]
    assert d.sequence(3, 12).tolist() == [
        368, 355, 419, 310, 249, 214, 185, 325, 456, 71, 295, 457]
    assert d.sequence(10_000_000, 12).tolist() == [
        84, 85, 351, 425, 368, 385, 486, 107, 256, 308, 207, 224]


def test_large_vocab_memory_is_bounded():
    """A 128k vocab builds no (v, v) matrix: the row memo stays within
    its byte budget however many distinct rows a sequence visits."""
    d = SyntheticLM(DataConfig(vocab_size=128256))
    seq = d.sequence(40_000_000, 200)
    assert seq.min() >= 0 and seq.max() < 128256
    assert len(d._rows) * 8 * 128256 <= SyntheticLM.ROW_MEMO_BYTES


def test_plan_mesh():
    p = plan_mesh(256, model=16, old_data=16)
    assert (p.data, p.idle_chips) == (16, 0)
    p = plan_mesh(252, model=16, old_data=16)  # one host (4 chips) died
    assert p.data == 15 and p.used_chips == 240 and p.idle_chips == 12
    p = plan_mesh(512, model=16, old_data=16, pods=2)
    assert p.data == 16 and p.pods == 2
