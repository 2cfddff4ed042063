"""Paged KV-cache tests: pool accounting, fuzzed lifecycle invariants,
token-exactness vs the dense engine, preemption, failover, determinism."""

import dataclasses

import numpy as np
import pytest
from conftest import direct_greedy, tiny_model

from repro.serving import (
    DenseSlotCache,
    PagedKVCache,
    PageError,
    PagePool,
    PipelineServer,
)


class TestCacheManagers:
    """The KVCacheManager contract both engines schedule against."""

    def test_dense_is_one_page_per_slot(self):
        mgr = DenseSlotCache(n_slots=2, max_len=32)
        assert mgr.fits(32) and not mgr.fits(33)
        assert mgr.capacity_weight() == 2
        s0 = mgr.reserve(7, 10)
        assert mgr.capacity_weight() == 1
        # Dense extending never fails within max_len...
        assert mgr.try_extend(7, s0, 32)
        # ...and a context submit should have rejected raises loudly.
        with pytest.raises(PageError):
            mgr.try_extend(7, s0, 33)
        s1 = mgr.reserve(8, 4)
        assert not mgr.can_reserve(1)  # full
        mgr.release(7, s0)
        mgr.release(8, s1)
        assert mgr.capacity_weight() == 2
        mgr.check_conservation()

    def test_paged_reserve_extend_release(self):
        mgr = PagedKVCache(n_slots=4, max_len=64, page_size=4, n_pages=6)
        slot = mgr.reserve(1, 9)  # 3 pages
        assert mgr.held(1) == 3
        assert mgr.capacity_weight() == 3
        assert mgr.try_extend(1, slot, 12)  # still 3 pages
        assert mgr.held(1) == 3
        assert mgr.try_extend(1, slot, 13)  # grows to 4
        assert mgr.held(1) == 4
        slot2 = mgr.reserve(2, 8)  # takes the last 2 pages
        assert not mgr.try_extend(1, slot, 17)  # pool exhausted -> preempt
        mgr.release(2, slot2)
        assert mgr.try_extend(1, slot, 17)
        # Block-table row names exactly the held pages, scratch elsewhere.
        row = mgr.block_table[slot]
        assert sorted(row[: mgr.held(1)]) == sorted(mgr.pages[1])
        assert (row[mgr.held(1):] == mgr.pool.scratch).all()
        mgr.release(1, slot)
        assert mgr.pool.free_pages == mgr.pool.n_pages
        mgr.check_conservation()

    def test_paged_slot_only_reservation(self):
        """Failover re-placement reserves the slot with zero pages; the
        memory grows lazily at call time."""
        mgr = PagedKVCache(n_slots=2, max_len=32, page_size=4, n_pages=4)
        slot = mgr.reserve(5, 0)
        assert mgr.held(5) == 0
        assert (mgr.block_table[slot] == mgr.pool.scratch).all()
        assert mgr.try_extend(5, slot, 7)
        assert mgr.held(5) == 2
        mgr.release(5, slot)
        mgr.check_conservation()

    def test_paged_row_overflow_raises(self):
        mgr = PagedKVCache(n_slots=1, max_len=16, page_size=4, n_pages=8)
        slot = mgr.reserve(1, 4)
        with pytest.raises(PageError):  # 17 entries > 4-page row
            mgr.try_extend(1, slot, 17)


class TestPagePool:
    def test_alloc_free_conservation(self):
        pool = PagePool(8, 4)
        a = pool.alloc(3, rid=1)
        b = pool.alloc(5, rid=2)
        assert pool.free_pages == 0 and len(set(a) | set(b)) == 8
        pool.check_conservation()
        pool.free(a, rid=1)
        assert pool.free_pages == 3
        pool.check_conservation()

    def test_double_free_and_foreign_free_raise(self):
        pool = PagePool(4, 4)
        a = pool.alloc(2, rid=1)
        pool.free(a, rid=1)
        with pytest.raises(PageError):
            pool.free(a, rid=1)  # double free
        b = pool.alloc(1, rid=2)
        with pytest.raises(PageError):
            pool.free(b, rid=3)  # foreign free

    def test_overdraw_raises(self):
        pool = PagePool(2, 4)
        assert not pool.can_alloc(3)
        with pytest.raises(PageError):
            pool.alloc(3, rid=0)

    def test_blocks_for(self):
        pool = PagePool(8, 16)
        assert pool.blocks_for(0) == 0  # an empty context holds no page
        assert pool.blocks_for(16) == 1
        assert pool.blocks_for(17) == 2
        assert pool.scratch == 8


def _assert_page_invariants(server: PipelineServer):
    """Conservation + exclusivity across the whole fleet, every step."""
    for (g, r), mgr in server.managers.items():
        mgr.check_conservation()  # pool conservation + single ownership
        resident = {
            req.rid
            for req in server._active
            if req.replicas is not None and req.replicas[g] == r
        }
        owners = {rid for rid, pages in mgr.pages.items() if pages}
        assert owners <= resident, (
            f"manager ({g},{r}) holds pages for non-residents "
            f"{sorted(owners - resident)}"
        )
        held = sum(len(p) for p in mgr.pages.values())
        assert mgr.pool.used_pages == held
        assert mgr.pool.free_pages + mgr.pool.used_pages == mgr.pool.n_pages


class TestPagedEngine:
    def test_token_exact_vs_dense_engine(self):
        """Acceptance: the paged engine is token-exact vs the dense PR 2
        engine on an identical workload (and vs monolithic greedy)."""
        cfg, model, params = tiny_model()
        n_tok = 4
        prompts = [(np.arange(6) * (i + 1) + i) % cfg.vocab_size for i in range(3)]

        def serve(paged):
            server = PipelineServer(
                model, params, n_groups=2, n_replicas=1,
                harvest_bounds=(50.0, 60.0), max_len=64, max_batch=4,
                paged=paged, page_size=8, seed=5,
            )
            reqs = [server.submit(p, n_tokens=n_tok) for p in prompts]
            for _ in range(400):
                if all(r.done for r in reqs):
                    break
                server.step()
            assert all(r.done for r in reqs)
            return server, reqs

        d_server, d_reqs = serve(False)
        p_server, p_reqs = serve(True)
        for d, p, prompt in zip(d_reqs, p_reqs, prompts):
            assert p.generated == d.generated
            assert p.generated == direct_greedy(model, params, prompt, n_tok)
        # Same dispatch accounting: one paged decode per (stage, round).
        assert p_server.stats.decode_calls == d_server.stats.decode_calls
        # Fully drained fleet returns every page.
        for mgr in p_server.managers.values():
            mgr.check_conservation()
            assert mgr.pool.free_pages == mgr.pool.n_pages

    def test_preemption_on_page_exhaustion(self):
        """A pool too small for every context preempts the youngest back
        to the queue (no crash, no drop) and still finishes token-exact."""
        cfg, model, params = tiny_model()
        server = PipelineServer(
            model, params, n_groups=1, n_replicas=1,
            harvest_bounds=(50.0, 60.0), max_len=64, max_batch=4,
            paged=True, page_size=4, max_pages=6, seed=0,
        )
        prompts = [(np.arange(6) + i) % cfg.vocab_size for i in range(3)]
        # 6 prompt + 12 generated = 18 entries -> 5 pages each; pool = 6.
        reqs = [server.submit(p, n_tokens=12) for p in prompts]
        for _ in range(3000):
            if all(r.done for r in reqs):
                break
            server.step()
            _assert_page_invariants(server)
        assert all(r.done for r in reqs)
        assert server.stats.preempted_jobs > 0
        assert server.stats.dropped_jobs == 0
        for r, p in zip(reqs, prompts):
            assert r.generated == direct_greedy(model, params, p, 12)

    def test_context_beyond_max_len_rejected_at_submit(self):
        """Regression: prompt + n_tokens > max_len used to overflow the
        block-table row mid-decode and crash the whole fleet (dense mode
        silently corrupted the cache tail). Both engines now reject."""
        cfg, model, params = tiny_model()
        for paged in (False, True):
            server = PipelineServer(
                model, params, n_groups=1, n_replicas=1,
                harvest_bounds=(50.0, 60.0), max_len=32, max_batch=2,
                paged=paged, page_size=8, seed=0,
            )
            req = server.submit(np.arange(30), n_tokens=8)
            assert req is None
            assert server.stats.dropped_jobs == 1
            ok = server.submit(np.arange(6), n_tokens=8)  # fits: admitted
            assert ok is not None and not ok.dropped
            for _ in range(200):
                if ok.done:
                    break
                server.step()
            assert ok.done

    def test_oversized_request_rejected_at_submit(self):
        """A request whose *final* context can never fit the pool is
        rejected up front — admitting it would only preempt healthy
        residents on the way to an inevitable mid-decode drop."""
        cfg, model, params = tiny_model()
        server = PipelineServer(
            model, params, n_groups=1, n_replicas=1,
            harvest_bounds=(50.0, 60.0), max_len=64, max_batch=2,
            paged=True, page_size=4, max_pages=2, seed=0,
        )
        # 6 prompt + 8 generated = 14 entries -> 4 pages > 2-page pool.
        req = server.submit(np.arange(6), n_tokens=8)
        assert req is None
        assert server.stats.dropped_jobs == 1
        _assert_page_invariants(server)

    def test_unadmittable_prompt_rejected_not_queue_blocking(self):
        """Regression: a prompt whose pages can never fit the pool used
        to park at the FIFO head forever, starving everything behind
        it. It is rejected at submit; later requests still run."""
        cfg, model, params = tiny_model()
        server = PipelineServer(
            model, params, n_groups=1, n_replicas=1,
            harvest_bounds=(50.0, 60.0), max_len=64, max_batch=2,
            paged=True, page_size=4, max_pages=2, seed=0,
        )
        big = server.submit(np.arange(12), n_tokens=4)  # 3 pages > 2-page pool
        assert big is None
        assert server.stats.dropped_jobs == 1
        small = server.submit(np.arange(4), n_tokens=4)
        assert small is not None
        for _ in range(200):
            if small.done:
                break
            server.step()
        assert small.done
        _assert_page_invariants(server)

    def test_readmission_reserves_full_context(self):
        """Regression: a preempted request re-admits with pages for its
        whole prefix (prompt + generated), not just the prompt — an
        under-reserved re-admit would immediately preempt healthy
        residents again (churn)."""
        cfg, model, params = tiny_model()
        server = PipelineServer(
            model, params, n_groups=1, n_replicas=1,
            harvest_bounds=(50.0, 60.0), max_len=64, max_batch=4,
            paged=True, page_size=4, max_pages=6, seed=0,
        )
        prompts = [(np.arange(6) + i) % cfg.vocab_size for i in range(3)]
        reqs = [server.submit(p, n_tokens=12) for p in prompts]
        for _ in range(3000):
            if all(r.done for r in reqs):
                break
            server.step()
            # Admission (including re-admission after preemption) must
            # reserve the whole prefix up front: before its first
            # prefill a resident holds blocks for prompt + generated,
            # not just the prompt.
            for req in server._active:
                if req.generated and not any(req.cache_ready):
                    need = server.managers[(0, 0)].pool.blocks_for(
                        len(req.prompt) + len(req.generated)
                    )
                    for g in range(server.G):
                        mgr = server.managers[(g, req.replicas[g])]
                        assert mgr.held(req.rid) >= need
        assert all(r.done for r in reqs)
        assert server.stats.preempted_jobs > 0

    def test_failover_token_exact_and_pages_released(self):
        cfg, model, params = tiny_model()
        server = PipelineServer(
            model, params, n_groups=2, n_replicas=3,
            harvest_bounds=(50.0, 60.0), max_len=64, max_batch=2,
            paged=True, page_size=8, seed=4,
        )
        prompt = np.arange(6) % cfg.vocab_size
        req = server.submit(prompt, n_tokens=5)
        fails = 0
        for _ in range(600):
            if req.done:
                break
            if fails < 2 and len(req.generated) > fails:
                server.fail_replica(0, req.replicas[0])
                fails += 1
            server.step()
        assert req.done and fails == 2
        assert server.stats.rerouted_stages >= 2
        assert req.generated == direct_greedy(model, params, prompt, 5)
        for mgr in server.managers.values():
            mgr.check_conservation()
            assert mgr.pool.free_pages == mgr.pool.n_pages

    def test_paged_requires_uniform_full_attention(self):
        cfg, model, params = tiny_model("hymba-1.5b")
        with pytest.raises(ValueError, match="paged"):
            PipelineServer(model, params, n_groups=1, n_replicas=1, paged=True)

    def test_seed_determinism(self):
        """Two paged runs with the same seed produce identical token
        streams and stats (page allocation is deterministic)."""
        cfg, model, params = tiny_model()

        def run():
            server = PipelineServer(
                model, params, n_groups=2, n_replicas=2,
                harvest_bounds=(8.0, 14.0), max_len=64, max_batch=2,
                paged=True, page_size=8, max_pages=8, seed=11,
            )
            stats = server.run(40, arrival_p=0.7, prompt_len=6, n_tokens=3)
            tokens = sorted(
                (r.rid, tuple(r.generated))
                for r in server._active + list(server._pending)
            )
            return dataclasses.asdict(stats), tokens

        s1, t1 = run()
        s2, t2 = run()
        assert s1 == s2
        assert t1 == t2


class TestPagedLifecycleFuzz:
    """Drive the paged fleet through random admit / complete /
    fail_replica / recover_replica sequences; pages must be conserved —
    no leaks, no double frees, free + resident == pool — after every
    step."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_lifecycle_conserves_pages(self, seed):
        cfg, model, params = tiny_model()
        G, R = 2, 2
        server = PipelineServer(
            model, params, n_groups=G, n_replicas=R,
            harvest_bounds=(12.0, 20.0), max_len=32, max_batch=2,
            paged=True, page_size=4, max_pages=10, seed=seed,
        )
        rng = np.random.default_rng(1000 + seed)
        submitted = []
        for step in range(80):
            u = rng.uniform()
            if u < 0.35:
                prompt_len = int(rng.integers(2, 9))
                n_tok = int(rng.integers(1, 5))
                req = server.submit(
                    rng.integers(0, cfg.vocab_size, size=prompt_len),
                    n_tokens=n_tok,
                )
                if req is not None:
                    submitted.append(req)
            elif u < 0.45:
                server.fail_replica(int(rng.integers(G)), int(rng.integers(R)))
            elif u < 0.60:
                server.recover_replica(int(rng.integers(G)), int(rng.integers(R)))
            server.step()
            _assert_page_invariants(server)
        # Recover everything and drain; all pages must come home.
        for g in range(G):
            for r in range(R):
                server.recover_replica(g, r)
        for _ in range(1500):
            if not server._active and not server._pending:
                break
            server.step()
            _assert_page_invariants(server)
        assert not server._active and not server._pending
        for mgr in server.managers.values():
            assert mgr.pool.free_pages == mgr.pool.n_pages
        stats = server.stats
        assert stats.submitted == stats.completed_jobs + stats.dropped_jobs
