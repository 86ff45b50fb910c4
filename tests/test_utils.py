"""BufferMap, QuorumWatermark(Vector), TopOne/TopK, and their device twins.

Mirrors util/ tests: BufferMapTest, QuorumWatermarkTest,
QuorumWatermarkVectorTest, TopOneTest, TopKTest.
"""

import numpy as np
import pytest

from frankenpaxos_tpu.ops.watermark import (
    contiguous_prefix_length,
    quorum_watermark,
    quorum_watermark_vector,
)
from frankenpaxos_tpu.utils import (
    BufferMap,
    QuorumWatermark,
    QuorumWatermarkVector,
    TopK,
    TopOne,
    VertexIdLike,
)


class TestBufferMap:
    def test_get_put(self):
        m = BufferMap(grow_size=4)
        assert m.get(0) is None
        m.put(3, "c")
        m.put(0, "a")
        m.put(10, "k")  # beyond grow_size: grows
        assert m.get(3) == "c"
        assert m.get(0) == "a"
        assert m.get(10) == "k"
        assert m.get(5) is None
        assert m.contains(10)
        assert not m.contains(11)

    def test_garbage_collect(self):
        m = BufferMap(grow_size=4)
        for i in range(8):
            m.put(i, str(i))
        m.garbage_collect(5)
        assert m.get(4) is None          # collected
        assert m.get(5) == "5"
        m.put(4, "resurrect")            # below watermark: dropped
        assert m.get(4) is None
        m.garbage_collect(3)             # watermark never regresses
        assert m.get(4) is None
        assert m.watermark == 5

    @pytest.mark.parametrize("key, held", [(2, "2"), (5, "5"), (1, None),
                                           (9, None), (500, None)])
    def test_pop_takes_a_value_out_once(self, key, held):
        m = BufferMap(grow_size=4)
        for i in range(6):
            m.put(i, str(i))
        m.garbage_collect(2)
        assert m.pop(key) == held
        assert m.pop(key) is None and m.get(key) is None
        assert m.to_dict() == {k: str(k) for k in range(2, 6) if k != key}

    def test_items(self):
        m = BufferMap()
        m.put(1, "b")
        m.put(4, "e")
        assert list(m.items()) == [(1, "b"), (4, "e")]
        m.garbage_collect(2)
        assert m.to_dict() == {4: "e"}


class TestQuorumWatermark:
    def test_doc_example(self):
        # util/QuorumWatermark.scala:9-25.
        qw = QuorumWatermark(num_watermarks=4)
        for i, w in enumerate([4, 3, 6, 2]):
            qw.update(i, w)
        assert qw.watermark(quorum_size=4) == 2
        assert qw.watermark(quorum_size=3) == 3
        assert qw.watermark(quorum_size=2) == 4
        assert qw.watermark(quorum_size=1) == 6

    def test_monotone_updates(self):
        qw = QuorumWatermark(num_watermarks=2)
        qw.update(0, 5)
        qw.update(0, 3)  # ignored: watermarks only increase
        assert qw.watermark(1) == 5

    def test_bounds(self):
        qw = QuorumWatermark(num_watermarks=2)
        with pytest.raises(ValueError):
            qw.watermark(0)
        with pytest.raises(ValueError):
            qw.watermark(3)


class TestQuorumWatermarkVector:
    def test_doc_example(self):
        # util/QuorumWatermarkVector.scala:5-20.
        qwv = QuorumWatermarkVector(n=4, depth=3)
        for i, v in enumerate([[1, 2, 3], [3, 2, 1], [2, 4, 6], [7, 5, 3]]):
            qwv.update(i, v)
        assert qwv.watermark(quorum_size=1) == [7, 5, 6]
        assert qwv.watermark(quorum_size=2) == [3, 4, 3]
        assert qwv.watermark(quorum_size=4) == [1, 2, 1]


def test_device_quorum_watermark_matches_host():
    rng = np.random.default_rng(7)
    for _ in range(20):
        n = int(rng.integers(1, 8))
        ws = rng.integers(0, 100, size=n)
        qw = QuorumWatermark(n)
        for i, w in enumerate(ws):
            qw.update(i, int(w))
        for k in range(1, n + 1):
            got = int(quorum_watermark(np.asarray(ws), np.int32(k)))
            assert got == qw.watermark(k)


def test_device_quorum_watermark_vector():
    mat = np.array([[1, 2, 3], [3, 2, 1], [2, 4, 6], [7, 5, 3]])
    np.testing.assert_array_equal(quorum_watermark_vector(mat, 2), [3, 4, 3])


def test_contiguous_prefix_length():
    assert int(contiguous_prefix_length(np.array([True, True, False, True]))) == 2
    assert int(contiguous_prefix_length(np.array([False, True]))) == 0
    assert int(contiguous_prefix_length(np.array([True] * 5))) == 5


VLIKE = VertexIdLike(leader_index=lambda v: v[0], id=lambda v: v[1])


class TestTopOne:
    def test_put_get(self):
        t = TopOne(num_leaders=3, like=VLIKE)
        t.put((0, 5))
        t.put((0, 2))
        t.put((2, 7))
        assert t.get() == [6, 0, 8]  # max id + 1 per leader

    def test_merge(self):
        a = TopOne(2, VLIKE)
        b = TopOne(2, VLIKE)
        a.put((0, 3))
        b.put((0, 1))
        b.put((1, 9))
        a.merge_equals(b)
        assert a.get() == [4, 10]


class TestTopK:
    def test_put_get(self):
        t = TopK(k=2, num_leaders=2, like=VLIKE)
        for vid in [(0, 1), (0, 5), (0, 3), (1, 2)]:
            t.put(vid)
        assert t.get() == [[3, 5], [2]]

    def test_merge(self):
        a = TopK(2, 1, VLIKE)
        b = TopK(2, 1, VLIKE)
        for i in [1, 4]:
            a.put((0, i))
        for i in [2, 8]:
            b.put((0, i))
        a.merge_equals(b)
        assert a.get() == [[4, 8]]
