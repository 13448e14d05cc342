"""Checkpoint store semantics (paper §6.1): async one-sided writes with
sequence numbers, out-of-order tolerance, commit-watermark prefix rule,
per-request restoration."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need hypothesis (CI)
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.checkpoint import CheckpointStore, KVCheckpointer


def _seg(i):
    return np.full((4,), i, np.float32)


def test_in_order_commit():
    s = CheckpointStore()
    s.register_request("r", aw_id=0)
    for i in range(5):
        s.async_update("r", i, _seg(i), seq_no=s.next_seq("r"),
                       token_value=100 + i)
    c, tv, segs = s.restore_request("r")
    assert c == 4 and tv == 104 and sorted(segs) == [0, 1, 2, 3, 4]


def test_out_of_order_waits_for_gap():
    """A later segment arriving before an earlier one must NOT advance the
    commit watermark past the gap (the 'async log + commit record' rule)."""
    s = CheckpointStore()
    s.register_request("r", aw_id=0)
    seqs = [s.next_seq("r") for _ in range(4)]
    s.async_update("r", 0, _seg(0), seqs[0], 100)
    s.async_update("r", 2, _seg(2), seqs[2], 102)   # seq 1 missing
    s.async_update("r", 3, _seg(3), seqs[3], 103)
    assert s.committed_token("r") == 0
    assert s.stats.out_of_order >= 2
    c, tv, segs = s.restore_request("r")
    assert c == 0 and sorted(segs) == [0]
    # restoration truncated the log to the watermark: seqs 2-3 are gone, so
    # filling the gap commits only token 1
    s.async_update("r", 1, _seg(1), seqs[1], 101)
    assert s.committed_token("r") == 1
    c, tv, segs = s.restore_request("r")
    assert c == 1 and tv == 101 and sorted(segs) == [0, 1]


def test_gap_fill_commits_contiguous_range():
    """Without a restore in between, filling the gap moves the watermark
    over the whole contiguous range that arrived early."""
    s = CheckpointStore()
    s.register_request("r", aw_id=0)
    seqs = [s.next_seq("r") for _ in range(4)]
    for t in (0, 2, 3):
        s.async_update("r", t, _seg(t), seqs[t], 100 + t)
    assert s.committed_token("r") == 0
    s.async_update("r", 1, _seg(1), seqs[1], 101)
    assert s.committed_token("r") == 3


@given(st.permutations(list(range(8))))
@settings(max_examples=30, deadline=None)
def test_any_arrival_order_full_prefix_restores_all(order):
    """Once every seq in a prefix has arrived (any order), the watermark
    covers it; segments beyond the last contiguous seq are never restored."""
    s = CheckpointStore()
    s.register_request("r", aw_id=0)
    seqs = [s.next_seq("r") for _ in range(8)]
    delivered = []
    for seq in order:
        s.async_update("r", seq, _seg(seq), seqs[seq], seq)
        delivered.append(seq)
        expect = -1
        got = set()
        for q in sorted(delivered):
            if q == expect + 1:
                expect = q
            got.add(q)
        assert s.committed_token("r") == expect
    c, tv, segs = s.restore_request("r")
    assert c == 7 and len(segs) == 8


def test_checkpointer_reorder_window_still_commits():
    s = CheckpointStore()
    ck = KVCheckpointer(s, aw_id=0, reorder_window=4, seed=1)
    ck.register("r")
    for i in range(16):
        ck.checkpoint_token("r", i, _seg(i), token_value=i)
    ck.flush()
    assert s.committed_token("r") == 15


def test_restore_accounting_bytes():
    s = CheckpointStore()
    s.register_request("r", aw_id=0)
    for i in range(3):
        s.async_update("r", i, [_seg(i), _seg(i)], s.next_seq("r"), i)
    before = s.stats.bytes_restored
    s.restore_request("r")
    assert s.stats.bytes_restored - before == 3 * 2 * 16


def test_reassign_and_release():
    s = CheckpointStore()
    s.register_request("a", aw_id=0)
    s.register_request("b", aw_id=0)
    s.register_request("c", aw_id=1)
    assert s.active_requests_on(0) == ["a", "b"]
    s.reassign("a", 1)
    assert s.active_requests_on(0) == ["b"]
    assert sorted(s.active_requests_on(1)) == ["a", "c"]
    s.release("a")
    assert s.active_requests_on(1) == ["c"]
