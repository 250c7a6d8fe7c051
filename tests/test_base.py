"""Unit tests for realhf_tpu.base (datapack, name_resolve, timeutil,
seeding, monitor). Mirrors the unit-test tier of the reference suite."""

import time

import numpy as np
import pytest

from realhf_tpu.base import datapack, name_resolve, seeding, timeutil
from realhf_tpu.base import monitor


class TestDatapack:

    @pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
    def test_min_abs_diff_partition_valid(self, k):
        rng = np.random.RandomState(0)
        for _ in range(10):
            n = rng.randint(k, 4 * k + 10)
            lens = rng.randint(1, 512, size=(n,))
            parts = datapack.min_abs_diff_partition(lens, k)
            # contiguous, non-empty, covering
            assert parts[0][0] == 0 and parts[-1][1] == n
            for (s0, e0), (s1, e1) in zip(parts[:-1], parts[1:]):
                assert e0 == s1
            assert all(e > s for s, e in parts)

    def test_partition_balance_quality(self):
        lens = np.array([100] * 64)
        parts = datapack.min_abs_diff_partition(lens, 8)
        sums = [lens[s:e].sum() for s, e in parts]
        assert max(sums) == min(sums) == 800

    def test_partition_min_size(self):
        lens = np.array([1000, 1, 1, 1, 1, 1])
        parts = datapack.min_abs_diff_partition(lens, 3, min_size=2)
        assert all(e - s >= 2 for s, e in parts)

    def test_partition_errors(self):
        with pytest.raises(ValueError):
            datapack.min_abs_diff_partition([1, 2], 3)
        with pytest.raises(ValueError):
            datapack.min_abs_diff_partition(np.ones((2, 2)), 1)

    def test_reorder_to_balanced_batches(self):
        rng = np.random.RandomState(0)
        lens = rng.randint(10, 1000, size=(96,))
        order, max_diff = datapack.reorder_to_balanced_batches(lens, 16)
        assert sorted(order.tolist()) == list(range(96))
        # With n divisible by batch size, every bin has exactly 16 seqs, so
        # consecutive windows of 16 are the bins; token sums differ <= max_diff.
        batch_tokens = [lens[order[i:i + 16]].sum() for i in range(0, 96, 16)]
        assert max(batch_tokens) - min(batch_tokens) == max_diff
        assert max_diff < lens.sum() // 6  # far better than random order

    def test_ffd_allocate(self):
        vals = [5, 3, 3, 2, 2, 1]
        groups = datapack.ffd_allocate(vals, capacity=6)
        assert sorted(datapack.flat2d(groups)) == list(range(6))
        for g in groups:
            assert sum(vals[i] for i in g) <= 6

    def test_flat2d(self):
        assert datapack.flat2d([[1, 2], [3], []]) == [1, 2, 3]


class TestNameResolve:

    def test_add_get_delete(self):
        name_resolve.add("a/b/c", "v1")
        assert name_resolve.get("a/b/c") == "v1"
        with pytest.raises(name_resolve.NameEntryExistsError):
            name_resolve.add("a/b/c", "v2")
        name_resolve.add("a/b/c", "v2", replace=True)
        assert name_resolve.get("a/b/c") == "v2"
        name_resolve.delete("a/b/c")
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            name_resolve.get("a/b/c")

    def test_subtree(self):
        name_resolve.add("root/x/1", "a")
        name_resolve.add("root/x/2", "b")
        name_resolve.add("root/y", "c")
        assert name_resolve.get_subtree("root/x") == ["a", "b"]
        assert len(name_resolve.find_subtree("root")) == 3
        name_resolve.clear_subtree("root/x")
        assert name_resolve.get_subtree("root/x") == []

    def test_subentry_and_wait(self):
        name_resolve.add_subentry("peers", "p0")
        name_resolve.add_subentry("peers", "p1")
        assert sorted(name_resolve.get_subtree("peers")) == ["p0", "p1"]
        with pytest.raises(TimeoutError):
            name_resolve.wait("nonexistent", timeout=0.2)

    def test_nfs_backend(self, tmp_path):
        repo = name_resolve.NfsNameRecordRepository(str(tmp_path / "nr"))
        repo.add("exp/trial/peer/0", "addr0")
        repo.add("exp/trial/peer/1", "addr1")
        assert repo.get("exp/trial/peer/0") == "addr0"
        assert repo.get_subtree("exp/trial/peer") == ["addr0", "addr1"]
        assert repo.find_subtree("exp/trial/peer") == [
            "exp/trial/peer/0", "exp/trial/peer/1"]
        repo.delete("exp/trial/peer/0")
        with pytest.raises(name_resolve.NameEntryNotFoundError):
            repo.get("exp/trial/peer/0")
        repo.reset()
        assert repo.get_subtree("exp/trial/peer") == []


class TestTimeutil:

    def test_frequency_steps(self):
        ctl = timeutil.FrequencyControl(frequency_steps=3)
        assert [ctl.check() for _ in range(7)] == [
            False, False, True, False, False, True, False]

    def test_frequency_seconds(self):
        ctl = timeutil.FrequencyControl(frequency_seconds=0.05)
        assert not ctl.check()
        time.sleep(0.06)
        assert ctl.check()

    def test_initial_value(self):
        ctl = timeutil.FrequencyControl(frequency_steps=10, initial_value=True)
        assert ctl.check()
        assert not ctl.check()

    def test_epoch_step_time(self):
        ctl = timeutil.EpochStepTimeFreqCtl(freq_epoch=None, freq_step=2, freq_sec=None)
        assert not ctl.check(epochs=0, steps=1)
        assert ctl.check(epochs=0, steps=1)


class TestSeeding:

    def test_derive(self, seeded):
        s1 = seeding.derive_seed("worker", "0")
        s2 = seeding.derive_seed("worker", "1")
        assert s1 != s2
        assert s1 == seeding.derive_seed("worker", "0")
        k = seeding.derive_key("model")
        assert k.shape == (2,)


class TestMonitor:

    def test_flops_positive_and_scaling(self):
        kw = dict(n_layers=4, hidden_dim=128, n_q_heads=8, n_kv_heads=8,
                  head_dim=16, intermediate_dim=512, vocab_size=1000)
        f1 = monitor.transformer_forward_flops(seqlens=[128] * 4, **kw)
        f2 = monitor.transformer_forward_flops(seqlens=[128] * 8, **kw)
        assert f2 == 2 * f1
        assert monitor.transformer_train_flops(seqlens=[128], **kw) == \
            3 * monitor.transformer_forward_flops(seqlens=[128], **kw)

    def test_span_times_what_it_wraps(self):
        from realhf_tpu.obs import tracing
        tracing.reset_default()
        try:
            tracing.start()
            with tracing.span("fwd"):
                time.sleep(0.01)
            capture = tracing.stop()
        finally:
            tracing.reset_default()
        [fwd] = capture.named("fwd")
        assert fwd["end"] - fwd["start"] >= 0.01
