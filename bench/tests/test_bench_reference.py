"""The plain reference, against hand-worked sequences."""

import numpy as np

from harness import semantics as S
from harness.reference import Reference

I, D, Q, R, N = S.OP_INSERT, S.OP_DELETE, S.OP_SEARCH, S.OP_RANGE, S.OP_NOP
NF, TOMB = S.NOT_FOUND, S.TOMBSTONE


def replay(ref, ops, ts=None, **kw):
    codes, keys, vals = (np.array(x) for x in zip(*ops))
    ts = np.arange(len(ops)) if ts is None else np.asarray(ts)
    return ref.replay(codes, keys, vals, ts, **kw)


def loaded():
    return Reference(np.array([30, 10, 20]), np.array([300, 100, 200]))


def test_point_operations_return_the_value_before_them():
    out, pages = replay(loaded(), [
        (Q, 10, 0),      # loaded
        (I, 10, 111),    # returns 100, writes 111
        (Q, 10, 0),      # 111
        (D, 15, 0),      # delete of an absent key: NOT_FOUND
        (Q, 15, 0),      # still absent
        (D, 20, 0),      # returns 200, tombstone
        (Q, 20, 0),      # NOT_FOUND
        (I, 20, 222),    # re-insert after the tombstone: returns NOT_FOUND
        (Q, 20, 0),      # 222
        (N, 0, 0),       # NOP
    ])
    assert out.tolist() == [100, 100, 111, NF, NF, 200, NF, NF, 222, NF]
    assert pages == {}


def test_ranges_see_exactly_the_operations_before_their_timestamp():
    ref = loaded()
    out, pages = replay(ref, [
        (R, 10, 30),     # t0: the loaded state
        (D, 20, 0),      # t1
        (I, 25, 250),    # t2
        (R, 10, 30),     # t3: 20 gone, 25 in
        (I, 20, 201),    # t4: re-insert
        (R, 15, 25),     # t5: bounds are inclusive
        (R, 31, 99),     # t6: empty
    ])
    assert out.tolist() == [3, 200, NF, 3, NF, 2, 0]
    assert pages[0].tolist() == [[10, 100], [20, 200], [30, 300]]
    assert pages[3].tolist() == [[10, 100], [25, 250], [30, 300]]
    assert pages[5].tolist() == [[20, 201], [25, 250]]
    assert pages[6].shape == (0, 2)
    assert ref.final_values(np.array([10, 20, 25, 99])).tolist() == \
        [100, 201, 250, NF]


def test_operations_run_in_timestamp_order_not_list_order():
    out, _ = replay(loaded(), [(Q, 10, 0), (I, 10, 5)], ts=[7, 3])
    assert out.tolist() == [5, 100]


def test_the_control_does_not_see_earlier_writes_of_its_batch():
    ops = [(I, 40, 4), (Q, 40, 0), (R, 10, 99), (Q, 40, 0)]
    sound, _ = replay(loaded(), ops)
    stale, pages = replay(loaded(), ops, stale_batch=3)
    assert sound.tolist() == [NF, 4, 4, 4]
    # t1 and t2 share t0's batch and miss its insert; t3 starts a new one
    assert stale.tolist() == [NF, NF, 3, 4]
    assert pages[2].tolist() == [[10, 100], [20, 200], [30, 300]]
