"""Property-based ``rollback(n)`` validation (hypothesis): arbitrary
interleavings of reserve / extend / rollback / release against the
dense and paged KV cache managers must conserve memory after every
operation — free + allocated pages is exactly the pool capacity, a
rolled-back context holds exactly ``blocks_for(new_len)`` pages, and
the block-table row mirrors the held pages with everything beyond them
re-scratched (a freed lane must never alias a live page). The
speculative engine leans on this: every accept finalizer and every
aborted round rewinds optimistic KV advances through ``rollback``."""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed (test extra)")
from hypothesis import example, given, settings
from hypothesis import strategies as st

from repro.serving.cache import DenseSlotCache, PagedKVCache

SETTINGS = dict(max_examples=25, deadline=None)

# One op = (kind, rid-pick, length-ish). Interpreted against the live
# set at replay time so every generated sequence is applicable.
_OPS = st.lists(
    st.tuples(
        st.sampled_from(["reserve", "extend", "rollback", "release"]),
        st.integers(0, 7),
        st.integers(0, 48),
    ),
    min_size=1,
    max_size=60,
)


def _replay(mgr, ops, paged):
    live = {}
    next_rid = 0
    for kind, pick, length in ops:
        if kind == "reserve":
            if mgr.free_slots() > 0 and mgr.can_reserve(length):
                slot = mgr.reserve(next_rid, length)
                # The engine stamps the host mirror at dispatch time;
                # the replay plays that role here.
                mgr.lengths[slot] = length
                live[next_rid] = slot
                next_rid += 1
        elif live:
            rid = sorted(live)[pick % len(live)]
            slot = live[rid]
            if kind == "extend":
                if mgr.try_extend(rid, slot, length):
                    mgr.lengths[slot] = max(int(mgr.lengths[slot]), length)
            elif kind == "rollback":
                n = length % (int(mgr.lengths[slot]) + 1)
                mgr.rollback(rid, slot, n)
                if paged and n > 0:
                    # Rollback trims the claim to exactly the shorter
                    # context's page need.
                    new_len = int(mgr.lengths[slot])
                    need = mgr.pool.blocks_for(new_len)
                    assert len(mgr.pages.get(rid, [])) == need
            else:
                mgr.release(rid, live.pop(rid))
        mgr.check_conservation()
        for rid, slot in live.items():
            assert mgr.slots[slot] == rid
            n = int(mgr.lengths[slot])
            assert 0 <= n <= mgr.max_len
            if paged:
                held = mgr.pages.get(rid, [])
                # Pages always cover the committed mirror, and the
                # block-table row mirrors them with a re-scratched tail
                # (a freed lane must never alias a live page).
                assert len(held) >= mgr.pool.blocks_for(n)
                row = list(mgr.block_table[slot])
                assert row[: len(held)] == held
                assert all(p == mgr.pool.scratch for p in row[len(held):])
    for rid, slot in list(live.items()):
        mgr.release(rid, slot)
    mgr.check_conservation()
    if paged:
        assert mgr.pool.free_pages == mgr.pool.n_pages


@given(_OPS, st.sampled_from([4, 8, 16]), st.integers(6, 24))
@settings(**SETTINGS)
def test_paged_rollback_property(ops, page_size, n_pages):
    _replay(
        PagedKVCache(n_slots=3, max_len=48, page_size=page_size,
                     n_pages=n_pages),
        ops, paged=True,
    )


@given(_OPS)
@settings(**SETTINGS)
def test_dense_rollback_property(ops):
    _replay(DenseSlotCache(n_slots=3, max_len=48), ops, paged=False)


@given(_OPS, st.integers(0, 2**31 - 1))
@example(ops=[("reserve", 0, 0)], seed=2)  # try_extend(0) once took a page
@settings(**SETTINGS)
def test_rollback_then_rewrite_is_exact(ops, seed):
    """The engine's actual usage: rollback(n) then re-extend to the same
    length lands the context on pages that cover exactly the same
    positions — lengths and page math agree with a shadow model."""
    mgr = PagedKVCache(n_slots=2, max_len=48, page_size=8, n_pages=12)
    rng = np.random.default_rng(seed)
    slot = mgr.reserve(0, 0)
    length = 0
    for _, _, amount in ops:
        if rng.uniform() < 0.5:
            target = min(48, length + amount % 9)
            if mgr.try_extend(0, slot, target):
                length = max(length, target)
                mgr.lengths[slot] = length
        else:
            n = amount % (length + 1)
            mgr.rollback(0, slot, n)
            length -= n
        assert int(mgr.lengths[slot]) == length
        assert len(mgr.pages.get(0, [])) == mgr.pool.blocks_for(length)
        mgr.check_conservation()
