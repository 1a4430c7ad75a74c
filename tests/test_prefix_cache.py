"""Paged-KV block manager (serving/prefix_cache.py): refcounted
physical allocation, token-reservation admission, block-hashed
zero-copy prefix aliasing, LRU eviction — and a randomized invariant
battery over a seeded mixed workload (the allocator must never
double-free, never alias a page to two diverged writers, and free
everything on release+invalidate)."""

import numpy as np
import pytest

from kubeflow_tpu.serving.prefix_cache import BlockManager


def toks(*vals):
    return np.asarray(vals, np.int32)


def run_request(mgr, tokens, budget):
    """One request's whole pool lifecycle, the way the engine drives
    it: admit (alias + reserve worst case), take every reserved page,
    publish the full-block prefix, release.  Returns (blocks, cached,
    res) with the pages still HELD (caller releases)."""
    need = -(-(len(tokens) + budget) // mgr.block)
    plan = mgr.admit(np.asarray(tokens, np.int32), len(tokens) - 1, need)
    if plan is None:
        return None
    shared, cached = plan
    blocks = list(shared)
    res = need - len(shared)
    while len(blocks) < need:
        blocks.append(mgr.take())
        res -= 1
    mgr.publish(np.asarray(tokens, np.int32), len(tokens), blocks)
    return blocks, cached, res


class TestBlockManager:
    def test_admit_reserve_take_release_roundtrip(self):
        mgr = BlockManager(num_blocks=8, block_tokens=2)
        plan = mgr.admit(toks(1, 2, 3, 4), 3, 4)
        assert plan == ([], 0)  # cold: no alias, 4 reserved
        assert mgr.available() == 4
        blocks = [mgr.take() for _ in range(4)]
        assert len(set(blocks)) == 4
        assert mgr.used_blocks() == 4
        mgr.release(blocks)
        assert mgr.used_blocks() == 0
        assert mgr.available() == 8
        mgr.check_invariants()

    def test_take_without_reservation_is_a_bug(self):
        mgr = BlockManager(num_blocks=2, block_tokens=2)
        with pytest.raises(RuntimeError):
            mgr.take()

    def test_admission_refused_when_pool_cannot_cover(self):
        mgr = BlockManager(num_blocks=4, block_tokens=2)
        assert mgr.admit(toks(1, 2), 1, 3) is not None
        # 1 block of headroom left; a 2-block request must hold.
        assert mgr.admit(toks(3, 4), 1, 2) is None
        # ... until the first request unreserves.
        mgr.release([], unreserve=3)
        assert mgr.admit(toks(3, 4), 1, 2) is not None
        mgr.check_invariants()

    def test_longest_block_prefix_aliases_zero_copy(self):
        mgr = BlockManager(num_blocks=16, block_tokens=2)
        out = run_request(mgr, [1, 2, 3, 4, 5, 6], 2)
        blocks, cached, res = out
        assert cached == 0
        # Full three-block prefix published; a sharer aliases the SAME
        # physical pages (zero-copy is literal: identical block ids).
        plan = mgr.admit(toks(1, 2, 3, 4, 5, 6, 7), 6, 4)
        shared, cached2 = plan
        assert cached2 == 6 and shared == blocks[:3]
        # limit forces >= 1 recomputed token: only 2 blocks match.
        plan = mgr.admit(toks(1, 2, 3, 4, 5, 6), 5, 3)
        assert plan[1] == 4 and plan[0] == blocks[:2]
        # Divergence after one block aliases one block (chained
        # digests: a shared MIDDLE block never matches alone).
        plan = mgr.admit(toks(1, 2, 9, 9), 3, 2)
        assert plan[1] == 2 and plan[0] == blocks[:1]
        plan = mgr.admit(toks(9, 2, 3, 4), 3, 2)
        assert plan == ([], 0)
        mgr.check_invariants()

    def test_partial_trailing_block_never_published(self):
        mgr = BlockManager(num_blocks=8, block_tokens=4)
        run_request(mgr, [1, 2, 3, 4, 5, 6], 2)
        plan = mgr.admit(toks(1, 2, 3, 4, 5, 6, 7, 8), 7, 2)
        assert plan[1] == 4  # only the full block matched

    def test_aliased_pages_survive_writer_release(self):
        """The capturing request retires while a sharer still aliases
        the pages: they must stay resident (refcount), and free only
        when BOTH the sharer and the record let go."""
        mgr = BlockManager(num_blocks=4, block_tokens=2)
        blocks, _, res = run_request(mgr, [1, 2, 3, 4], 0)
        shared, cached = mgr.admit(toks(1, 2, 3, 4), 3, 2)
        assert cached == 2 and shared == blocks[:1]
        mgr.release(blocks, unreserve=res)  # writer gone
        mgr.check_invariants()
        # The aliased page is still resident (sharer + record hold it).
        assert shared[0] not in mgr._free
        mgr.release(shared, unreserve=2 - len(shared))
        mgr.check_invariants()
        # Record-held pages remain as evictable cache, not leaked.
        assert mgr.used_blocks() == 2  # the two published pages
        mgr.invalidate()
        assert mgr.used_blocks() == 0

    def test_lru_eviction_frees_only_unreferenced(self):
        mgr = BlockManager(num_blocks=4, block_tokens=2)
        a, _, ra = run_request(mgr, [1, 1, 1, 1], 0)
        mgr.release(a, unreserve=ra)
        b, _, rb = run_request(mgr, [2, 2, 2, 2], 0)
        mgr.release(b, unreserve=rb)
        # Pool full of cached pages; a fresh 2-block request must evict
        # the LRU record (a's) — b's stays.
        plan = mgr.admit(toks(3, 3, 3, 3), 3, 2)
        assert plan == ([], 0)
        c = [mgr.take(), mgr.take()]
        assert mgr.evictions == 1 and mgr.block_evictions == 2
        assert set(c) == set(a)  # a's pages were recycled
        assert mgr.admit(toks(1, 1, 1, 1), 3, 0) == ([], 0)  # a gone
        plan = mgr.admit(toks(2, 2, 2, 2), 3, 2)
        assert plan[1] == 2  # b still served
        mgr.check_invariants()

    def test_record_evicted_mid_use_keeps_pages_resident(self):
        mgr = BlockManager(num_blocks=4, block_tokens=2)
        a, _, ra = run_request(mgr, [1, 1, 1, 1], 0)
        mgr.release(a, unreserve=ra)
        shared, cached = mgr.admit(toks(1, 1, 1, 1), 3, 1)
        assert cached == 2
        # Force eviction pressure (a 3-block request against 2 free
        # pages): the record dies, but the page the sharer still
        # aliases must NOT free out from under it.
        b, _, rb = run_request(mgr, [2, 2, 2, 2, 2, 2], 0)
        assert mgr.evictions == 1
        assert mgr.block_evictions == 1  # only the unreferenced page
        for blk in shared:
            assert blk not in mgr._free
        mgr.release(shared)
        mgr.release(b, unreserve=rb)
        mgr.check_invariants()

    def test_digest_collision_first_writer_wins(self):
        mgr = BlockManager(num_blocks=8, block_tokens=2)
        a, _, ra = run_request(mgr, [1, 2, 3, 4], 0)
        # Cache OFF lookup path for the duplicate: publish the same
        # chain from different physical pages (racing captures).
        plan = mgr.admit(toks(9, 9, 9, 9), 3, 2)
        dup = [mgr.take(), mgr.take()]
        mgr.publish(toks(1, 2, 3, 4), 4, dup)
        # The established record keeps serving the digests.
        shared, cached = mgr.admit(toks(1, 2, 3, 4, 5), 4, 3)
        assert cached == 4 and shared == a[:2]
        mgr.release(shared, unreserve=1)
        mgr.release(a, unreserve=ra)
        mgr.release(dup)
        mgr.check_invariants()

    def test_caching_off_is_pure_allocator(self):
        mgr = BlockManager(num_blocks=4, block_tokens=2, caching=False)
        blocks, cached, res = run_request(mgr, [1, 2, 3, 4], 0)
        assert cached == 0
        mgr.release(blocks, unreserve=res)
        assert mgr.admit(toks(1, 2, 3, 4), 3, 2) == ([], 0)
        assert mgr.used_blocks() == 0  # publish was a no-op
        mgr.check_invariants()

    def test_invalidate_forgets_everything(self):
        mgr = BlockManager(num_blocks=4, block_tokens=2)
        blocks, _, res = run_request(mgr, [1, 2, 3, 4], 0)
        mgr.release(blocks, unreserve=res)
        shared, cached = mgr.admit(toks(1, 2, 3, 4), 3, 1)
        assert cached == 2
        mgr.release(shared)  # the sharer retires before the reload
        mgr.invalidate()
        assert mgr.admit(toks(1, 2, 3, 4), 3, 0) == ([], 0)
        assert mgr.used_blocks() == 0
        assert mgr.stats()["published_records"] == 0
        mgr.check_invariants()

    def test_validation(self):
        with pytest.raises(ValueError):
            BlockManager(num_blocks=0, block_tokens=2)
        with pytest.raises(ValueError):
            BlockManager(num_blocks=1, block_tokens=0)


class TestAllocatorInvariantBattery:
    """Seeded randomized mixed workload against a small pool: admit /
    grow / release / publish in arbitrary
    interleavings.  After EVERY operation the structural invariants
    must hold (no double-free, refcount/free-list agreement,
    reservation coverage), no page may ever be writable by two
    diverged requests at once, and a full drain + invalidate must
    return every page."""

    @pytest.mark.parametrize("seed", [0, 1, 7])
    def test_mixed_workload_never_corrupts(self, seed):
        rng = np.random.RandomState(seed)
        mgr = BlockManager(num_blocks=12, block_tokens=4)
        live = []  # dicts: tokens, blocks, shared_n, res_left, need

        def writable(req):
            # Pages this request may WRITE: its private (taken) pages.
            # Aliased prefix pages are read-only by construction — the
            # engine starts its first write at the block-aligned
            # cached offset, which always lands in a private page.
            return set(req["blocks"][req["shared_n"]:])

        for _ in range(400):
            op = rng.randint(3)
            if op == 0 and len(live) < 6:  # admit
                # Half the prompts share one of two hot prefixes so
                # aliasing actually happens; suffixes diverge.
                base = ([1, 2, 3, 4, 5, 6, 7, 8] if rng.randint(2)
                        else [9, 9, 9, 9])
                tokens = (base * 2)[:rng.randint(4, 13)] + \
                    rng.randint(10, 90, size=(rng.randint(0, 5),)
                                ).tolist()
                budget = int(rng.randint(1, 9))
                need = -(-(len(tokens) + budget) // mgr.block)
                plan = mgr.admit(np.asarray(tokens, np.int32),
                                 len(tokens) - 1, need)
                if plan is not None:
                    shared, cached = plan
                    assert cached <= len(tokens) - 1
                    assert len(shared) * mgr.block == cached
                    live.append({
                        "tokens": tokens, "blocks": list(shared),
                        "shared_n": len(shared),
                        "res_left": need - len(shared), "need": need,
                        "published": False})
            elif op == 1 and live:  # grow the frontier
                req = live[rng.randint(len(live))]
                if req["res_left"] > 0:
                    blk = mgr.take()
                    req["res_left"] -= 1
                    # Exclusive ownership at take(): no other live
                    # request may hold (let alone write) this page.
                    for other in live:
                        if other is not req:
                            assert blk not in other["blocks"], (
                                "page aliased to a diverged writer")
                    req["blocks"].append(blk)
                    if not req["published"] and (
                            len(req["blocks"]) * mgr.block
                            >= len(req["tokens"])):
                        mgr.publish(
                            np.asarray(req["tokens"], np.int32),
                            len(req["tokens"]), req["blocks"])
                        req["published"] = True
            elif op == 2 and live:  # retire
                req = live.pop(rng.randint(len(live)))
                mgr.release(req["blocks"], unreserve=req["res_left"])
            # Writable sets of any two live requests stay disjoint.
            for i, a in enumerate(live):
                for b in live[i + 1:]:
                    assert not (writable(a) & writable(b))
            mgr.check_invariants()

        for req in live:
            mgr.release(req["blocks"], unreserve=req["res_left"])
        mgr.check_invariants()
        mgr.invalidate()
        assert mgr.used_blocks() == 0, "pages leaked after full drain"
        assert mgr.available() == mgr.num_blocks

    @pytest.mark.parametrize("seed", [0, 1, 7])
    def test_spill_workload_never_corrupts(self, seed):
        """The two-tier battery (§5.10): the device workload above
        interleaved with spill / park (host_put) / fetch
        (lookup_spilled) ops against a small host tier, invariants
        checked across BOTH tiers after every op.  Host records hold
        COPIES keyed by the same chained digests — never device block
        ids — so a page can never be device-writable and host-spilled
        at once; the payload marker asserts lookups return the exact
        record stored for that chain depth."""
        rng = np.random.RandomState(seed)
        mgr = BlockManager(num_blocks=12, block_tokens=4,
                           host_blocks=8)
        live = []
        spilled_chains = []  # (tokens, depth_blocks) once host-stored

        def writable(req):
            return set(req["blocks"][req["shared_n"]:])

        def payload_for(digests):
            return {"marker": digests[-1], "n": len(digests)}

        for _ in range(400):
            op = rng.randint(6)
            if op == 0 and len(live) < 6:  # admit
                base = ([1, 2, 3, 4, 5, 6, 7, 8] if rng.randint(2)
                        else [9, 9, 9, 9])
                tokens = (base * 2)[:rng.randint(4, 13)] + \
                    rng.randint(10, 90, size=(rng.randint(0, 5),)
                                ).tolist()
                budget = int(rng.randint(1, 9))
                need = -(-(len(tokens) + budget) // mgr.block)
                plan = mgr.admit(np.asarray(tokens, np.int32),
                                 len(tokens) - 1, need)
                if plan is not None:
                    shared, cached = plan
                    live.append({
                        "tokens": tokens, "blocks": list(shared),
                        "shared_n": len(shared),
                        "res_left": need - len(shared), "need": need,
                        "published": False})
            elif op == 1 and live:  # grow the frontier
                req = live[rng.randint(len(live))]
                if req["res_left"] > 0:
                    blk = mgr.take()
                    req["res_left"] -= 1
                    for other in live:
                        if other is not req:
                            assert blk not in other["blocks"], (
                                "page aliased to a diverged writer")
                    req["blocks"].append(blk)
                    if not req["published"] and (
                            len(req["blocks"]) * mgr.block
                            >= len(req["tokens"])):
                        mgr.publish(
                            np.asarray(req["tokens"], np.int32),
                            len(req["tokens"]), req["blocks"])
                        req["published"] = True
            elif op == 2 and live:  # retire
                req = live.pop(rng.randint(len(live)))
                mgr.release(req["blocks"], unreserve=req["res_left"])
            elif op == 3:  # spill an idle LRU record to the host tier
                for rec in mgr.spill_candidates(max_records=2):
                    digests = list(rec.digests)
                    freed = mgr.spill(rec, payload_for(digests))
                    if freed is None:
                        continue  # declined: stale or unstorable
                    # The freed pages are back in the free list — a
                    # double-free of any of them would trip
                    # check_invariants' free-list uniqueness below.
                    assert 0 <= freed <= len(digests)
                    assert digests[-1] in mgr._host_chains
            elif op == 4:  # park a session's KV straight to host
                tokens = rng.randint(1, 90,
                                     size=(rng.randint(4, 17),)).tolist()
                depth = len(tokens) // mgr.block
                if depth:
                    dig = payload_for(
                        [b"x"] * depth)  # marker only needs depth
                    stored = mgr.host_put(
                        np.asarray(tokens, np.int32), len(tokens),
                        {"marker": None, "n": depth})
                    if stored:
                        spilled_chains.append((tokens, stored))
            elif op == 5 and spilled_chains:  # fetch / re-import path
                tokens, depth = spilled_chains[
                    rng.randint(len(spilled_chains))]
                payload, got = mgr.lookup_spilled(
                    np.asarray(tokens, np.int32), len(tokens))
                if payload is not None:  # may have been host-evicted
                    assert 0 < got <= depth
                    assert payload["n"] >= got
                    mgr.spills_in += got  # the engine's re-import
            for i, a in enumerate(live):
                for b in live[i + 1:]:
                    assert not (writable(a) & writable(b))
            mgr.check_invariants()

        for req in live:
            mgr.release(req["blocks"], unreserve=req["res_left"])
        mgr.check_invariants()
        mgr.invalidate()
        assert mgr.used_blocks() == 0, "pages leaked after full drain"
        assert mgr.host_used_blocks() == 0, "host pages survived drain"
        assert mgr.available() == mgr.num_blocks

    def test_spill_preserves_available_and_declines_unstorable(self):
        """Spilling an idle record moves its pages cached->free, so
        available() is UNCHANGED (the deadlock-freedom invariant
        free + evictable + spillable >= reserved holds across tiers)
        — and a record larger than the whole host tier is declined
        outright rather than destroying the only copy."""
        mgr = BlockManager(num_blocks=8, block_tokens=4, host_blocks=2)
        tokens = toks(*range(1, 13))  # 3 full blocks
        got = run_request(mgr, tokens, budget=0)
        assert got is not None
        blocks, _, res = got
        mgr.release(blocks, unreserve=res)
        before = mgr.available()
        [rec] = [r for r in (mgr.spill_candidates(2) or [])] or [None]
        # 3 blocks > host_blocks=2: candidates must skip it entirely
        # (the pages still count as spillable mass — they are idle —
        # but no candidate offers them, so the engine destroy-evicts).
        assert rec is None
        assert mgr.spillable_blocks() == 3
        # Enlarge the tier: now it spills, available() is unchanged.
        mgr.host_blocks = 4
        [rec] = mgr.spill_candidates(1)
        freed = mgr.spill(rec, {"p": 1})
        assert freed == 3
        assert mgr.available() == before
        assert mgr.host_used_blocks() == 3
        payload, depth = mgr.lookup_spilled(tokens, len(tokens))
        assert payload == {"p": 1} and depth == 3
        mgr.check_invariants()
