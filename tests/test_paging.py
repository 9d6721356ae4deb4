"""Paged KV cache with shared-prefix reuse (repro.runtime.paging).

Pool/prefix level: BlockPool free-list + refcount lifecycle, sha256 chain
keys, reclaimable parking and LRU reclaim, refcounting under interleaved
frees.

Kernel level: the block-table Pallas decode mode is BIT-identical to the
dense kernel at matching block size on shuffled physical page layouts
(same blocks streamed in the same order => same flash accumulation), and
executed-block counts still scale with ceil(length/bs).

Manager level: paged ``merge_prefill`` scatters prefill rows into pages
bit-exactly; gathering a slot's page chain reproduces the dense cache
row; prefix-cache hits skip the copy but read back identical KV.

Engine level: a paged engine decodes token-identically to a dense engine
(both attn impls; the dense run pins ``decode_bc`` to the page size for
kernel-blocking parity), eviction/re-admission round-trips leak no pages,
preemption under a deliberately tiny pool re-queues and completes every
request, and watermark hysteresis gates admission.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                gather_pages,
                                                paged_decode_attention_ref)
from repro.runtime import (BlockPool, PagedKVCacheManager, PrefixCache,
                           Request, RequestState, ServingEngine, chunk_keys)
from repro.runtime.kv import KVCacheManager

KEY = jax.random.PRNGKey(11)
BS = 16   # page size (min TPU lane tile)


def smoke_cfg(**kw):
    base = dict(name="paging-smoke", family="dense", num_layers=2,
                d_model=64, num_heads=4, num_kv_heads=2, ffn_dim=128,
                vocab_size=128, head_dim=16)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(autouse=True)
def _check_ledger_invariants(monkeypatch):
    """Every pool/manager any test in this module constructs (including
    the ones buried inside a ServingEngine) is invariant-checked at
    teardown: refcount leaks and double frees fail the scenario that
    caused them, not a later test as pool exhaustion."""
    pools, managers = [], []
    orig_pool, orig_mgr = BlockPool.__init__, PagedKVCacheManager.__init__

    def pool_init(self, *a, **kw):
        orig_pool(self, *a, **kw)
        pools.append(self)

    def mgr_init(self, *a, **kw):
        orig_mgr(self, *a, **kw)
        managers.append(self)

    monkeypatch.setattr(BlockPool, "__init__", pool_init)
    monkeypatch.setattr(PagedKVCacheManager, "__init__", mgr_init)
    yield
    owned = {id(kv.pool) for kv in managers}
    for kv in managers:
        kv.check_invariants()                 # includes kv.pool
    for pool in pools:
        if id(pool) not in owned:
            pool.check_invariants()


# ---------------------------------------------------------------------------
# BlockPool / PrefixCache units
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_alloc_free_roundtrip(self):
        pool = BlockPool(6, BS)
        assert pool.usable == 5
        pages = [pool.alloc() for _ in range(5)]
        assert pages == [1, 2, 3, 4, 5]       # deterministic low-first
        assert pool.alloc() is None
        assert pool.used_count() == 5
        for p in pages:
            assert pool.release(p) == 0
            pool.free(p)
        assert pool.free_count() == 5
        assert pool.frees == 5 and pool.allocs == 5

    def test_refcounts(self):
        pool = BlockPool(4, BS)
        p = pool.alloc()
        pool.retain(p)
        assert pool.ref(p) == 2
        assert pool.release(p) == 1           # still referenced: no free
        assert pool.release(p) == 0
        pool.free(p)
        assert pool.alloc() == p              # back on the free list

    def test_scratch_page_reserved(self):
        pool = BlockPool(3, BS)
        assert 0 not in [pool.alloc(), pool.alloc()]
        with pytest.raises(AssertionError):
            pool.free(0)

    def test_adopt_revives_reclaimed(self):
        pool = BlockPool(3, BS)
        p = pool.alloc()
        pool.release(p)       # refcount 0, NOT freed (caller parks it)
        pool.adopt(p)
        assert pool.ref(p) == 1


class TestPrefixCache:
    def test_chain_keys_commit_to_prefix(self):
        a = chunk_keys([1, 2, 3, 4, 5, 6], 2)
        b = chunk_keys([1, 2, 3, 4, 9, 9], 2)
        assert len(a) == 3
        assert a[:2] == b[:2] and a[2] != b[2]
        # partial tail chunks get no key
        assert len(chunk_keys([1, 2, 3], 2)) == 1
        assert chunk_keys([], 2) == []

    def test_park_and_reclaim_lru(self):
        pc = PrefixCache()
        ka, kb = chunk_keys([1, 2], 2)[0], chunk_keys([3, 4], 2)[0]
        pc.insert(ka, 5)
        pc.insert(kb, 6)
        pc.on_released(5)
        pc.on_released(6)
        pc.on_retained(6)                     # 6 re-shared: un-parked
        assert pc.reclaim() == 5              # oldest parked goes first
        assert pc.lookup(ka) is None          # key dropped: future misses
        assert pc.lookup(kb) == 6
        assert pc.reclaim() is None           # 6 is referenced again

    def test_refcounting_under_interleaved_free(self):
        """Three holders of one shared page freeing in arbitrary order:
        the page is parked exactly once, at the LAST release."""
        kv = PagedKVCacheManager(4, 64, block_size=BS)
        prompt = list(range(BS))              # exactly one full block
        slots = [kv.alloc() for _ in range(3)]
        for s in slots:
            kv.assign_blocks(s, prompt)
        page = int(kv._tables[slots[0], 0])
        assert all(int(kv._tables[s, 0]) == page for s in slots)
        assert kv.pool.ref(page) == 3
        for n_left, s in zip((2, 1, 0), (slots[1], slots[0], slots[2])):
            kv.free(s)
            assert kv.pool.ref(page) == n_left
        assert kv.prefix.reclaimable_count() == 1
        assert kv.pool.used_count() == 1      # parked, not leaked to 'used'


# ---------------------------------------------------------------------------
# kernel: block-table mode parity
# ---------------------------------------------------------------------------

def _paged_case(lengths, bs=BS, Kv=2, g=2, D=32, n_extra=3, seed=3):
    """Build a dense ragged cache + an equivalent SHUFFLED page layout."""
    B = len(lengths)
    H = Kv * g
    nmax = max((l + bs - 1) // bs for l in lengths) if any(lengths) else 1
    nmax = max(nmax, 1)
    C = nmax * bs
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Kv, C, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Kv, C, D), jnp.float32)

    n_blocks = sum((l + bs - 1) // bs for l in lengths)
    P = 1 + n_blocks + n_extra                # page 0 reserved
    rng = np.random.RandomState(seed)
    order = rng.permutation(np.arange(1, P)).tolist()
    kp = np.zeros((P, Kv, bs, D), np.float32)
    vp = np.zeros((P, Kv, bs, D), np.float32)
    tbl = np.full((B, nmax), -1, np.int32)
    for b, l in enumerate(lengths):
        for c in range((l + bs - 1) // bs):
            page = order.pop()
            tbl[b, c] = page
            kp[page] = np.asarray(k[b, :, c * bs:(c + 1) * bs])
            vp[page] = np.asarray(v[b, :, c * bs:(c + 1) * bs])
    lens = jnp.asarray(lengths, jnp.int32)
    return q, k, v, jnp.asarray(kp), jnp.asarray(vp), lens, jnp.asarray(tbl)


class TestPagedKernel:
    LENGTHS = [0, 1, BS + 1, 3 * BS, 4 * BS - 7]

    def test_paged_ref_matches_dense_ref(self):
        q, k, v, kp, vp, lens, tbl = _paged_case(self.LENGTHS)
        dense = decode_attention_ref(q, k, v, lens)
        paged = paged_decode_attention_ref(q, kp, vp, lens, tbl)
        assert jnp.array_equal(dense, paged)

    def test_paged_kernel_bitwise_vs_dense_kernel(self):
        """Same logical blocks, same order, same flash math => bit-equal
        to the dense kernel run at bc == page size."""
        q, k, v, kp, vp, lens, tbl = _paged_case(self.LENGTHS)
        dense = decode_attention_pallas(q, k, v, lens, bc=BS)
        paged = paged_decode_attention_pallas(q, kp, vp, lens, tbl)
        assert jnp.array_equal(dense, paged)

    def test_block_skip_counts(self):
        q, k, v, kp, vp, lens, tbl = _paged_case(self.LENGTHS)
        _, counts = paged_decode_attention_pallas(
            q, kp, vp, lens, tbl, return_block_counts=True)
        want = [(l + BS - 1) // BS for l in self.LENGTHS]
        assert np.asarray(counts)[:, 0].tolist() == want

    def test_kernel_close_to_oracle(self):
        q, k, v, kp, vp, lens, tbl = _paged_case(self.LENGTHS)
        out = paged_decode_attention_pallas(q, kp, vp, lens, tbl)
        ref = paged_decode_attention_ref(q, kp, vp, lens, tbl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)

    def test_gather_pages_clamps_unallocated(self):
        _, _, _, kp, vp, lens, tbl = _paged_case([BS, 2 * BS])
        dense = gather_pages(kp, tbl)
        assert dense.shape == (2, kp.shape[1], tbl.shape[1] * BS,
                               kp.shape[3])
        # row 0's unallocated tail entry gathered page 0 (zeros)
        assert not np.asarray(dense[0, :, BS:]).any()


# ---------------------------------------------------------------------------
# manager: prefill scatter parity, eviction round-trip
# ---------------------------------------------------------------------------

def _models(impl="xla", decode_bc=None):
    from repro.models.transformer import ExecutionContext, Model
    cfg = smoke_cfg()
    m_d = Model(cfg, ExecutionContext(attn_impl=impl, decode_bc=decode_bc),
                dtype=jnp.float32)
    m_p = Model(cfg, ExecutionContext(attn_impl=impl), dtype=jnp.float32)
    params = m_d.init(KEY)
    return cfg, m_d, m_p, params


class TestPagedManager:
    def test_prefill_scatter_bit_parity(self):
        """Gathering a paged slot's page chain reproduces the dense
        cache row exactly, including when the first block is a prefix
        hit (copy skipped, shared page already holds the bytes)."""
        cfg, m_d, m_p, params = _models()
        max_ctx = 64
        kv_d = KVCacheManager(3, max_ctx, m_d, dtype=jnp.float32)
        kv_p = PagedKVCacheManager(3, max_ctx, m_p, dtype=jnp.float32,
                                   block_size=BS)
        kv_d.ensure_caches(); kv_p.ensure_caches()
        rng = np.random.RandomState(5)
        toks = rng.randint(1, 128, size=(2, 40))
        toks[1, :BS] = toks[0, :BS]           # shared first block
        lens = [20, 33]
        _, pre = m_d.prefill(params, jnp.asarray(toks), seq_budget=max_ctx,
                             last_positions=jnp.asarray([19, 32]))
        for kv in (kv_d, kv_p):
            kv.take(0); kv.take(1)
        kv_d.merge_prefill([0, 1], pre, lens)
        kv_p.merge_prefill([0, 1], pre, lens,
                           tokens=[toks[0, :20].tolist(),
                                   toks[1, :33].tolist()])
        assert kv_p.paging.prefix_hit_tokens == BS    # row 1 block 0
        tbl = kv_p.table_array()
        for layer_d, layer_p in zip(kv_d.caches, kv_p.caches):
            for name in ("k", "v"):
                dense_rows = layer_d[name]
                paged_rows = gather_pages(layer_p[name], tbl)
                for slot, n in zip((0, 1), lens):
                    assert jnp.array_equal(dense_rows[slot, :, :n],
                                           paged_rows[slot, :, :n]), name
            assert jnp.array_equal(layer_p["index"][:2],
                                   jnp.asarray(lens, jnp.int32))

    def test_eviction_readmission_roundtrip_no_leak(self):
        kv = PagedKVCacheManager(4, 128, block_size=BS, num_blocks=17)
        rng = np.random.RandomState(9)
        prompts = [rng.randint(0, 100, size=n).tolist()
                   for n in (40, 25, 50)]
        for cycle in range(3):
            slots = []
            for p in prompts:
                s = kv.alloc()
                kv.assign_blocks(s, p)
                kv.set_length(s, len(p) + 1)
                slots.append(s)
            for s in slots:
                assert kv.ensure_decode_page(s) or True
                kv.free(s)
            # pages either free or parked-for-reuse; none leaked
            assert kv.pool.used_count() == kv.prefix.reclaimable_count()
        # cycles 2+ hit every full prefix block of every prompt
        full_blocks = sum(len(p) // BS for p in prompts)
        assert kv.paging.prefix_hit_blocks == 2 * full_blocks

    def test_admission_charge_discounts_cached(self):
        kv = PagedKVCacheManager(2, 128, block_size=BS)
        prompt = list(range(2 * BS + 5))
        new_pages, cached = kv.admission_charge(prompt)
        assert (new_pages, cached) == (3, 0)
        s = kv.alloc(); kv.assign_blocks(s, prompt)
        new_pages, cached = kv.admission_charge(prompt)
        assert (new_pages, cached) == (1, 2 * BS)  # only the private tail
        assert kv.cached_prefix_tokens(prompt) == 2 * BS

    def test_watermark_hysteresis(self):
        kv = PagedKVCacheManager(4, 128, block_size=BS, num_blocks=11,
                                 watermark_high=0.6, watermark_low=0.3)
        s = kv.alloc()
        kv._assign_private(s, 6 * BS)          # 7 of 10 usable pages
        assert kv.admission_blocked()
        kv.free(s)
        s2 = kv.alloc()
        kv._assign_private(s2, 3 * BS)         # 4/10: between low and high
        assert kv.admission_blocked()          # hysteresis: still blocked
        kv.free(s2)
        assert not kv.admission_blocked()      # below low: re-opened
        kv.free_count()                        # base slot API still works

    def test_pool_exhaustion_raises_and_rolls_back(self):
        kv = PagedKVCacheManager(2, 128, block_size=BS, num_blocks=3)
        s = kv.alloc()
        with pytest.raises(RuntimeError):
            kv.assign_blocks(s, list(range(5 * BS)))
        assert kv._nblk[s] == 0                # partial assignment undone
        assert kv.pool.free_count() == 2

    def test_capacity_at_fixed_budget_vs_dense(self):
        """At an 8192-token KV budget, conversations sharing a 256-token
        system prompt (32 private prompt tokens, 96 decoded) fit at least
        twice as many at once as dense rows of ``max_context`` would, and
        the shared prompt is served from the prefix cache."""
        max_context, bs, budget = 4096, 32, 8192
        kv = PagedKVCacheManager(256, max_context, block_size=bs,
                                 num_blocks=budget // bs + 1)  # +1 scratch
        shared = list(range(256))
        resident = 0
        while True:
            slot = kv.alloc()
            if slot is None:
                break
            prompt = shared + [10_000 + resident * 131 + i
                               for i in range(32)]
            try:
                kv.assign_blocks(slot, prompt[:-1])
            except RuntimeError:
                kv.free(slot)
                break
            kv.set_length(slot, len(prompt))
            decoded = 0
            # engine order: page the write at length-1, then advance
            while decoded < 96 and kv.ensure_decode_page(slot):
                kv.set_length(slot, kv.length(slot) + 1)
                decoded += 1
            if decoded < 96:
                kv.free(slot)
                break
            resident += 1
        dense = budget // max_context
        assert resident >= 2 * dense, (resident, dense)
        assert kv.paging_summary()["prefix_hit_rate"] > 0.0


# ---------------------------------------------------------------------------
# engine: end-to-end parity + preemption
# ---------------------------------------------------------------------------

def _engines(attn_impl, **paged_kw):
    cfg = smoke_cfg()
    common = dict(num_slots=4, max_context=128, dtype=jnp.float32, seed=0)
    e_d = ServingEngine(cfg, attn_impl=attn_impl, decode_bc=BS, **common)
    e_p = ServingEngine(cfg, params=e_d.params, attn_impl=attn_impl,
                        kv_layout="paged", kv_block_size=BS,
                        **paged_kw, **common)
    return e_d, e_p


def _requests():
    rng = np.random.RandomState(7)
    shared = rng.randint(1, 128, size=40).tolist()
    out = []
    for seed, n in ((1, 5), (2, 12), (3, 3), (4, 21)):
        tail = np.random.RandomState(seed).randint(1, 128, size=n).tolist()
        out.append(Request(prompt=shared + tail, max_new_tokens=6))
    return out


class TestPagedEngine:
    @pytest.mark.parametrize("attn_impl", ["xla", "decode_kernel"])
    def test_token_parity_vs_dense(self, attn_impl):
        e_d, e_p = _engines(attn_impl)
        for r in _requests():
            e_d.submit(r)
        for r in _requests():
            e_p.submit(r)
        fin_d = {len(r.prompt): r.output for r in e_d.run()}
        fin_p = {len(r.prompt): r.output for r in e_p.run()}
        assert fin_d == fin_p
        stats = e_p.paging_stats()
        assert stats["prefix_hit_tokens"] > 0      # shared system prompt
        assert stats["preemptions"] == 0
        assert e_d.paging_stats() is None

    def test_preemption_completes_all(self):
        """Pool sized so concurrent generations MUST preempt: every
        request still finishes with its full output, and preempted ones
        re-prefilled from resume_tokens."""
        cfg = smoke_cfg()
        eng = ServingEngine(cfg, num_slots=4, max_context=128,
                            dtype=jnp.float32, seed=0,
                            kv_layout="paged", kv_block_size=BS,
                            kv_num_blocks=9)
        rng = np.random.RandomState(11)
        reqs = [Request(prompt=rng.randint(1, 128, size=30).tolist(),
                        max_new_tokens=40) for _ in range(3)]
        for r in reqs:
            eng.submit(r)
        fin = eng.run(max_steps=500)
        assert len(fin) == 3
        assert all(r.state is RequestState.FINISHED for r in fin)
        assert all(len(r.output) == 40 for r in fin)
        assert eng.paging_stats()["preemptions"] >= 1
        assert sum(r.preemptions for r in fin) >= 1
        # pool fully drained at the end: nothing leaked
        ps = eng.paging_stats()
        assert ps["blocks_used"] == ps["blocks_reclaimable"]

    def test_oversized_for_pool_rejected(self):
        cfg = smoke_cfg()
        eng = ServingEngine(cfg, num_slots=2, max_context=128,
                            dtype=jnp.float32, kv_layout="paged",
                            kv_block_size=BS, kv_num_blocks=4)
        eng.submit(Request(prompt=list(range(1, 100)), max_new_tokens=2))
        fin = eng.run(max_steps=5)
        assert len(fin) == 1
        assert fin[0].state is RequestState.REJECTED
        assert "pages" in fin[0].error

    def test_paged_guard_rejects_unsupported(self):
        cfg = smoke_cfg(attention="sliding")
        # the decode kernel refuses a ring cache first; with the dense
        # attention path the paged manager's own guard is what raises
        with pytest.raises(ValueError, match="paged"):
            ServingEngine(cfg, kv_layout="paged", attn_impl="xla",
                          dtype=jnp.float32)
