"""Property tests for the incremental chain-axis uncertainty summaries.

The early-exit sampler (ISSUE 9) decides whether to retire a session's
surplus MC chains by comparing the uncertainty summary over a chain
*prefix* against the full set, both computed by the ``Running*Summary``
accumulators in ``repro.core.uncertainty``.  Two properties make that
decision trustworthy, and both are pinned here over randomized inputs:

1. **Batch agreement** — an accumulator fed all S chains finalizes to the
   same values (at fp32) as the batch formulas ``classification_summary``
   / ``regression_summary`` over the stacked ``[S, ...]`` array.
2. **Partition invariance** — any split of the chain axis into blocks,
   accumulated via ``update``/``merge`` in any grouping, agrees with the
   one-shot result (Chan's parallel rule; plain sums for classification).

Property-based via ``hypothesis`` when the environment has it; on minimal
installs ``tests/conftest.py`` provides a deterministic stand-in that
sweeps seeded examples through the same properties, so the coverage does
not silently vanish.  The strategies draw only a case *seed* — the case
shapes/values come from ``numpy.random.default_rng(seed)``, which both
the real and stand-in runners reproduce exactly.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.uncertainty import (ClassificationSummary,
                                    RegressionSummary,
                                    RunningClassificationSummary,
                                    RunningRegressionSummary,
                                    classification_summary,
                                    regression_summary)

# fp32 has ~7 decimal digits; the accumulators work in float64 and only
# round once at finalize, so agreement holds to a few ulps of the batch
# (fp32-accumulated) result's own error.
ATOL, RTOL = 1e-5, 1e-5


def _random_case(rng, *, regression: bool):
    s = int(rng.integers(2, 17))
    b = int(rng.integers(1, 4))
    scale = float(rng.uniform(0.1, 8.0))
    if regression:
        t, i = int(rng.integers(1, 6)), int(rng.integers(1, 3))
        means = rng.normal(0, scale, (s, b, t, i))
        log_vars = rng.normal(-1, 1, (s, b, t, i))
        return means, log_vars
    c = int(rng.integers(2, 7))
    return rng.normal(0, scale, (s, b, c))


def _partitions(rng, s):
    """A random composition of s into >=1 block sizes."""
    sizes, left = [], s
    while left > 0:
        k = int(rng.integers(1, left + 1))
        sizes.append(k)
        left -= k
    return sizes


def _assert_cls_close(got: ClassificationSummary,
                      want: ClassificationSummary):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=ATOL, rtol=RTOL)


def _assert_reg_close(got: RegressionSummary, want: RegressionSummary):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=ATOL, rtol=RTOL)


def _check_classification(logits, sizes):
    want = classification_summary(np.asarray(logits, np.float32))
    acc = RunningClassificationSummary()
    off = 0
    for k in sizes:
        acc.update(logits[off:off + k])
        off += k
    _assert_cls_close(acc.finalize(), want)
    # merge of independently-built accumulators agrees too
    merged = RunningClassificationSummary()
    off = 0
    for k in sizes:
        merged.merge(RunningClassificationSummary().update(
            logits[off:off + k]))
        off += k
    _assert_cls_close(merged.finalize(), want)


def _check_regression(means, log_vars, sizes):
    want = regression_summary(np.asarray(means, np.float32),
                              np.asarray(log_vars, np.float32))
    acc = RunningRegressionSummary()
    off = 0
    for k in sizes:
        acc.update(means[off:off + k], log_vars[off:off + k])
        off += k
    _assert_reg_close(acc.finalize(), want)
    merged = RunningRegressionSummary()
    off = 0
    for k in sizes:
        merged.merge(RunningRegressionSummary().update(
            means[off:off + k], log_vars[off:off + k]))
        off += k
    _assert_reg_close(merged.finalize(), want)


@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_classification_matches_batch_any_partition(seed):
    rng = np.random.default_rng(seed)
    logits = _random_case(rng, regression=False)
    _check_classification(logits, _partitions(rng, logits.shape[0]))


@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 2**32 - 1))
def test_regression_matches_batch_any_partition(seed):
    rng = np.random.default_rng(seed)
    means, log_vars = _random_case(rng, regression=True)
    _check_regression(means, log_vars, _partitions(rng, means.shape[0]))


class TestEdgeCases:
    def test_single_chain_prefix_then_rest(self):
        """The early-exit access pattern: prefix block, copy, fold rest."""
        rng = np.random.default_rng(7)
        logits = rng.normal(0, 3, (8, 2, 5))
        prefix = RunningClassificationSummary().update(logits[:4])
        full = prefix.copy().update(logits[4:])
        # the copy kept the prefix accumulator intact
        assert prefix.count == 4 and full.count == 8
        _assert_cls_close(prefix.finalize(),
                          classification_summary(
                              np.asarray(logits[:4], np.float32)))
        _assert_cls_close(full.finalize(),
                          classification_summary(
                              np.asarray(logits, np.float32)))

    def test_regression_without_log_vars(self):
        rng = np.random.default_rng(8)
        means = rng.normal(0, 2, (6, 1, 3, 1))
        want = regression_summary(np.asarray(means, np.float32), None)
        got = RunningRegressionSummary().update(means).finalize()
        _assert_reg_close(got, want)
        assert float(np.max(np.abs(np.asarray(got.aleatoric)))) == 0.0

    def test_identical_chains_give_exactly_zero_epistemic(self):
        """The zeros-traffic early-exit argument: identical chains mean
        exactly zero MI / epistemic variance — not merely tiny — so a 0.0
        threshold retires them and nothing else."""
        block = np.tile(np.arange(6.0)[None, None, :], (5, 1, 1))  # [5,1,6]
        cls = RunningClassificationSummary().update(block).finalize()
        assert float(np.asarray(cls.mutual_information)[0]) == 0.0
        means = np.tile(np.ones((1, 2, 3, 1)), (4, 1, 1, 1))
        reg = RunningRegressionSummary().update(means).finalize()
        assert float(np.max(np.asarray(reg.epistemic))) == 0.0

    def test_empty_finalize_raises(self):
        with pytest.raises(ValueError, match="no chains"):
            RunningClassificationSummary().finalize()
        with pytest.raises(ValueError, match="no chains"):
            RunningRegressionSummary().finalize()

    def test_bad_block_shapes_raise(self):
        with pytest.raises(ValueError, match=r"\[s, B, C\]"):
            RunningClassificationSummary().update(np.zeros((3, 4)))
        with pytest.raises(ValueError, match=r"\[s, "):
            RunningRegressionSummary().update(np.zeros(3))
