"""Paged-KV serving: allocator conservation, token-budget admission,
page-granular migration (the page-level bit-exactness contract), the v2
wire format, and the paged decode kernel vs its oracle.

The property harnesses are hand-rolled seeded sweeps (no hypothesis
dependency): the allocator churn runs >= 400 randomized trials with the
conservation invariant audited after every operation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.configs.tiny import make_tiny
from repro.core.migration import pack_slot, repack_slot, unpack_slot
from repro.kernels.decode_attention import (_paged_blocking,
                                           paged_decode_attention)
from repro.models.attention import paged_decode_attend
from repro.serving.engine import Engine, Request
from repro.serving.paged import PageAllocator, PagedEngine
from tests.helpers import synthetic_paged_snapshot

CFG = make_tiny(get("llama-1.5b"))
PARAMS = None


def _params():
    global PARAMS
    if PARAMS is None:
        from repro.models.init import init_params
        PARAMS = init_params(CFG, jax.random.key(0))
    return PARAMS


def mk_paged(seed=0, page_size=8, rows=4, pages=None, max_len=64):
    return PagedEngine(CFG, _params(), page_size=page_size, rows=rows,
                       pages=pages, max_len=max_len, seed=seed)


def mk_req(rid, prompt, max_new=8, **kw):
    return Request(rid, np.asarray(prompt), max_new_tokens=max_new, **kw)


# -- PageAllocator conservation (hand-rolled property harness) ---------------

def test_page_allocator_conservation_400_trials():
    """>= 400 randomized alloc/free trials across pool sizes, with the
    full conservation invariant (free + owned == total, no page handed
    out twice, no page both free and owned) audited after EVERY
    operation, plus the never-partial-alloc and free-unowned-raises
    contracts."""
    trials = 0
    for seed in range(8):
        rng = np.random.default_rng(seed)
        total = int(rng.integers(1, 40))
        alloc = PageAllocator(total)
        held: dict[str, list[int]] = {}
        for op in range(60):
            trials += 1
            if rng.random() < 0.55 or not held:
                n = int(rng.integers(0, total + 4))
                owner = f"r{seed}-{op}"
                free_before = alloc.free_pages
                pages = alloc.alloc(n, owner)
                if n > free_before:
                    assert pages is None      # over-ask: all-or-nothing
                    assert alloc.free_pages == free_before  # no debris
                else:
                    assert pages is not None and len(pages) == n
                    assert len(set(pages)) == n, "page handed out twice"
                    for p in pages:
                        assert alloc.owners[p] == owner
                    if n:
                        held[owner] = pages
            else:
                owner = list(held)[int(rng.integers(len(held)))]
                alloc.free(held.pop(owner))
            alloc.check()
            assert alloc.free_pages + alloc.used_pages == total
            assert alloc.used_pages == sum(map(len, held.values()))
        # drain and re-verify the empty state
        for pages in held.values():
            alloc.free(pages)
        alloc.check()
        assert alloc.free_pages == total and not alloc.owners
    assert trials >= 400
    # freeing a page nobody owns raises loudly
    a = PageAllocator(4)
    got = a.alloc(2, "x")
    with pytest.raises(ValueError):
        a.free([3])
    a.free(got)
    with pytest.raises(ValueError):
        a.free(got)                           # double free


# -- token-budget admission ---------------------------------------------------

def test_paged_engine_admits_more_than_dense_at_equal_kv_memory():
    """The tentpole claim: at the same KV memory (dense 2 slots x 64
    rows == paged 16 pages x 8 slots), short requests admit 8-wide on
    the paged engine vs 2 on the dense grid -- and all of them decode
    to completion concurrently."""
    dense = Engine(CFG, _params(), slots=2, max_len=64, seed=0)
    paged = mk_paged(rows=10, page_size=8, pages=16)
    assert paged.pages * paged.page_size == dense.slots * dense.max_len

    def admit_all(eng):
        n = 0
        while eng.can_admit(6 + 8) and eng.add_request(
                mk_req(f"r{n}", np.arange(2, 8), max_new=8)):
            n += 1
        return n

    n_dense, n_paged = admit_all(dense), admit_all(paged)
    assert n_dense == 2
    assert n_paged == 8
    assert not paged.can_admit(6 + 8)      # page budget exhausted
    assert paged.free_slots                # ...but rows remain: pages gate
    paged.allocator.check()
    # every admitted request decodes to completion, concurrently
    done = set()
    for _ in range(10):
        done |= set(paged.step())
        paged.allocator.check()
    assert len(done) == 8
    assert paged.allocator.used_pages == 0 and not paged.requests


def test_admission_reserves_upfront_and_retire_returns_pages():
    """A request reserves ceil((prompt+max_new)/page_size) pages at
    admission (it can never deadlock mid-decode) and retirement returns
    exactly that reservation."""
    eng = mk_paged(rows=4, page_size=8, pages=6, max_len=64)
    assert eng.add_request(mk_req("a", np.arange(2, 8), max_new=10))
    assert eng.allocator.used_pages == 2   # ceil(16/8)
    # 4 free pages: a 3-page ask fits, a 5-page ask must be refused NOW
    assert eng.can_admit(24) and not eng.can_admit(33)
    assert not eng.add_request(mk_req("big", np.arange(2, 27), max_new=8))
    assert eng.allocator.used_pages == 2   # refused ask left no debris
    eng.allocator.check()
    row = next(iter(eng.requests))
    eng.retire(row)
    assert eng.allocator.used_pages == 0
    assert np.all(np.asarray(eng.state.page_table[row]) == -1)


def test_free_token_budget_and_admissible():
    eng = mk_paged(rows=2, page_size=8, pages=8, max_len=64)
    assert eng.free_token_budget == 64
    assert eng.admissible(64) and not eng.admissible(65)
    assert eng.add_request(mk_req("a", np.arange(2, 8), max_new=10))
    assert eng.free_token_budget == (8 - 2) * 8
    assert eng.add_request(mk_req("b", np.arange(2, 8), max_new=10))
    assert eng.free_token_budget == 0      # rows exhausted (B=2)
    # admissible() answers "could this EVER fit" -- it ignores current
    # occupancy so the rebalancer can park work toward this engine
    assert eng.admissible(40)


def test_paged_decode_is_deterministic_in_seed():
    outs = []
    for _ in range(2):
        eng = mk_paged(seed=3, rows=4, page_size=8)
        reqs = [mk_req(f"r{i}", np.arange(2 + i, 8 + i), max_new=8)
                for i in range(3)]
        for r in reqs:
            assert eng.add_request(r)
        while eng.requests:
            eng.step()
        outs.append([r.output for r in reqs])
    assert outs[0] == outs[1]


# -- engine-level conservation churn ------------------------------------------

def test_paged_engine_churn_conserves_pages():
    """Random admit/decode/retire/extract churn on one engine: the
    allocator invariant and the pages<->requests correspondence hold
    after every operation."""
    eng = mk_paged(seed=1, rows=4, page_size=8, pages=10, max_len=32)
    rng = np.random.default_rng(0)
    n = 0
    for op in range(120):
        r = rng.random()
        if r < 0.4:
            req = mk_req(f"c{n}", np.arange(2, 8), max_new=8)
            if eng.can_admit(6 + 8):
                assert eng.add_request(req)
                n += 1
            else:
                assert (not eng.free_slots
                        or eng.allocator.free_pages < 2)
        elif r < 0.7 and eng.requests:
            eng.step()
        elif r < 0.85 and eng.requests:
            eng.retire(next(iter(eng.requests)))
        elif eng.requests:
            row = next(iter(eng.requests))
            snap = eng.extract_slot(row)          # migration departure
            assert snap.version == 2
        eng.allocator.check()
        reserved = sum(len(eng._row_pages(row)) for row in eng.requests)
        assert eng.allocator.used_pages == reserved
    for row in list(eng.requests):
        eng.retire(row)
    eng.allocator.check()
    assert eng.allocator.used_pages == 0


# -- page-granular migration: the page-level contract -------------------------

def test_same_page_size_migration_is_bit_exact():
    """The page-level contract that replaces the dense path's slots=1
    workaround: same page size + same kernel program (rows, max_len) =>
    bit-exact resume, even when the destination's page POOL is a
    different size and differently occupied.  rows=1 keeps the solo
    oracle exact (batch-content sensitivity, see ROADMAP)."""
    prompt, max_new = np.arange(2, 8), 12
    baseline = mk_paged(seed=0, rows=1, page_size=8, pages=8)
    ref = mk_req("m", prompt, max_new=max_new)
    assert baseline.add_request(ref)
    while not ref.done:
        baseline.step()

    src = mk_paged(seed=0, rows=1, page_size=8, pages=8)
    req = mk_req("m", prompt, max_new=max_new)
    assert src.add_request(req)
    for _ in range(5):
        src.step()
    blob = pack_slot(src.extract_slot(req.slot))
    assert src.allocator.used_pages == 0   # departure freed the pages

    dst = mk_paged(seed=9, rows=1, page_size=8, pages=12)  # bigger pool
    snap = unpack_slot(blob, dst.slot_like())
    moved = dst.inject_slot(repack_slot(snap, dst.max_len))
    dst.allocator.check()
    while not moved.done:
        dst.step()
    assert moved.output == ref.output
    # the wire shipped live pages only: ceil(pos/ps) pages, not max_len
    n_live = snap.arrays.caches[0][0]["attn"]["k"].shape[1]
    assert n_live == -(-(len(prompt) + 5) // 8)


def test_cross_page_size_injection_rejected_loudly():
    src = mk_paged(seed=0, rows=1, page_size=8, pages=8)
    req = mk_req("x", np.arange(2, 8), max_new=8)
    assert src.add_request(req)
    src.step()
    snap = src.extract_slot(req.slot)
    dst = mk_paged(seed=1, rows=1, page_size=16, pages=4)
    with pytest.raises(ValueError, match="page_size mismatch"):
        dst.inject_slot(snap)
    dst.allocator.check()
    assert dst.allocator.used_pages == 0


def test_paged_engine_rejects_dense_v1_snapshot():
    dense = Engine(CFG, _params(), slots=1, max_len=64, seed=0)
    req = mk_req("d", np.arange(2, 8), max_new=8)
    assert dense.add_request(req)
    dense.step()
    snap = dense.extract_slot(req.slot)
    assert snap.version == 1
    paged = mk_paged(rows=1, page_size=8)
    with pytest.raises(ValueError, match="v2"):
        paged.inject_slot(snap)


# -- the v2 wire format -------------------------------------------------------

def test_v2_wire_roundtrip_sweep():
    """pack -> unpack -> pack is byte-identical for random v2 snapshot
    geometries (hand-rolled sweep), with the trace context riding."""
    for seed in range(24):
        rng = np.random.default_rng(seed)
        snap = synthetic_paged_snapshot(
            seed=seed, repeats=int(rng.integers(1, 3)),
            page_size=int(rng.choice([4, 8])),
            kv_heads=int(rng.integers(1, 3)),
            head_dim=int(rng.choice([4, 8])),
            plen=int(rng.integers(1, 6)),
            out_len=int(rng.integers(0, 4)),
            max_new=int(rng.integers(4, 9)))
        if seed % 3 == 0:
            snap.trace = {"trace_id": f"t{seed}", "span_id": seed}
        wire = pack_slot(snap)
        like = jax.eval_shape(lambda: snap.arrays)
        back = unpack_slot(wire, like)
        assert back.version == 2 and back.page_size == snap.page_size
        assert back.trace == snap.trace
        assert pack_slot(back) == wire


def test_v2_repack_is_budget_check_only():
    """repack_slot on a v2 snapshot never re-layouts (pages are
    position-addressed); it only enforces the tail-truncation bound."""
    snap = synthetic_paged_snapshot(seed=3, page_size=8, plen=5,
                                    out_len=2, max_new=6)
    need = int(snap.arrays.position) + snap.remaining_tokens
    assert repack_slot(snap, need) is snap
    assert repack_slot(snap, need + 100) is snap
    assert pack_slot(repack_slot(snap, need)) == pack_slot(snap)
    with pytest.raises(ValueError, match="truncation"):
        repack_slot(snap, need - 1)


def test_unknown_wire_version_rejected_loudly():
    snap = synthetic_paged_snapshot(seed=0)
    snap.version = 99
    blob = pack_slot(snap)
    like = jax.eval_shape(lambda: snap.arrays)
    with pytest.raises(ValueError, match="unknown pack_slot wire version"):
        unpack_slot(blob, like)


# -- paged decode kernel vs oracle --------------------------------------------

def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("P,ps,NP", [(8, 16, 4), (16, 8, 4), (6, 32, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["plain", "window", "softcap"])
def test_paged_decode_attention_sweep(P, ps, NP, dtype, mode):
    """pallas_call (interpret=True) vs the jnp oracle across pool
    geometries, including rows with dead (unmapped) page-table slots
    and a fully-dead row (whose output is defined as 0)."""
    rng = np.random.default_rng(11)
    B, H, KV, D = 3, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), dtype)
    k_pool = jnp.asarray(rng.standard_normal((P, ps, KV, D)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((P, ps, KV, D)), dtype)
    pt = np.full((B, NP), -1, np.int32)
    pt[0, :NP] = rng.choice(P, NP, replace=False)        # full table
    pt[1, :max(NP // 2, 1)] = rng.choice(P, max(NP // 2, 1),
                                         replace=False)  # partial
    pos = np.asarray([NP * ps - 1, ps + 1, 0], np.int32)
    pos[1] = min(pos[1], max(NP // 2, 1) * ps - 1)
    kw = {}
    if mode == "window":
        kw["window"] = ps + ps // 2
    elif mode == "softcap":
        kw["softcap"] = 20.0
    o = paged_decode_attention(q, k_pool, v_pool, jnp.asarray(pt),
                               jnp.asarray(pos), interpret=True, **kw)
    oref = paged_decode_attend(q, k_pool, v_pool, jnp.asarray(pt),
                               jnp.asarray(pos), page_size=ps, **kw)
    err = float(jnp.abs(o.astype(jnp.float32)
                        - oref.astype(jnp.float32)).max())
    assert err < _tol(dtype), (mode, err)
    assert float(jnp.abs(o[2]).max()) == 0.0  # fully-dead row is zeros


def test_paged_decode_attention_randomized_tables():
    """Randomized page tables/positions, interpret vs oracle."""
    P, ps, NP, B, H, KV, D = 12, 8, 3, 4, 2, 1, 64
    for seed in range(4):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        k_pool = jnp.asarray(rng.standard_normal((P, ps, KV, D)),
                             jnp.float32)
        v_pool = jnp.asarray(rng.standard_normal((P, ps, KV, D)),
                             jnp.float32)
        pt = np.full((B, NP), -1, np.int32)
        pos = np.zeros((B,), np.int32)
        perm = list(rng.permutation(P))
        for b in range(B):
            n = int(rng.integers(1, NP + 1))
            pt[b, :n] = [perm.pop() for _ in range(n)]
            pos[b] = int(rng.integers(0, n * ps))
        o = paged_decode_attention(q, k_pool, v_pool, jnp.asarray(pt),
                                   jnp.asarray(pos), interpret=True)
        oref = paged_decode_attend(q, k_pool, v_pool, jnp.asarray(pt),
                                   jnp.asarray(pos), page_size=ps)
        assert float(jnp.abs(o - oref).max()) < 2e-5, seed


@pytest.mark.parametrize("H", [8, 16], ids=["mha", "gqa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["plain", "window", "softcap"])
def test_paged_decode_attention_page_blocks(H, dtype, mode):
    """The kernel walks each row's live pages a block at a time: rows
    whose position falls mid-block, on the last key of a block and on
    the first of the next, mapped pages past the position (which must
    not count), an unmapped hole, an all -1 row (zeros), and a window
    that spans two blocks, over NP pages that are no multiple of the
    pages per block."""
    KV, D, ps, NP, P = 8, 128, 16, 40, 64
    ppb, kvb = _paged_blocking(ps, KV, D, jnp.dtype(dtype).itemsize, NP)
    assert kvb == KV and 1 < ppb < NP and NP % ppb, (ppb, NP)
    rng = np.random.default_rng(5)
    B = 6
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), dtype)
    k_pool = jnp.asarray(rng.standard_normal((P, ps, KV, D)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((P, ps, KV, D)), dtype)
    pt = rng.integers(0, P, (B, NP)).astype(np.int32)   # pages may repeat
    pos = np.asarray([
        (ppb + (NP - ppb) // 2) * ps + 7,  # mid-block, mapped pages after
        ppb * ps - 1,                      # last key of block 0
        ppb * ps + 2,                      # just into block 1
        NP * ps - 1,                       # whole table, last block short
        3 * ps + 5,                        # early, 36 mapped pages past it
        ppb * ps + 9,                      # all -1: dead row
    ], np.int32)
    pt[3, ppb - 2] = -1                                 # a hole
    pt[5] = -1
    kw = {}
    if mode == "window":
        kw["window"] = 3 * ps + 5    # row 2's window spans blocks 0, 1
    elif mode == "softcap":
        kw["softcap"] = 20.0
    o = paged_decode_attention(q, k_pool, v_pool, jnp.asarray(pt),
                               jnp.asarray(pos), interpret=True, **kw)
    oref = paged_decode_attend(q, k_pool, v_pool, jnp.asarray(pt),
                               jnp.asarray(pos), page_size=ps, **kw)
    assert o.shape == q.shape and o.dtype == q.dtype
    err = float(jnp.abs(o.astype(jnp.float32)
                        - oref.astype(jnp.float32)).max())
    assert err < _tol(dtype), (mode, err)
    assert float(jnp.abs(o[5]).max()) == 0.0


def test_paged_decode_attention_head_groups():
    """Where one page of every head outgrows a block, the heads split
    into groups along a second grid axis, each copying its own slice of
    every page."""
    KV, D, ps, NP, P, B = 32, 128, 128, 3, 5, 2
    ppb, kvb = _paged_blocking(ps, KV, D, 4, NP)
    assert kvb < KV, kvb
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((B, 1, KV, D)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((P, ps, KV, D)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((P, ps, KV, D)), jnp.float32)
    pt = jnp.asarray([[4, 0, 2], [1, 3, -1]], jnp.int32)
    pos = jnp.asarray([NP * ps - 1, ps + 40], jnp.int32)
    o = paged_decode_attention(q, k_pool, v_pool, pt, pos, interpret=True)
    oref = paged_decode_attend(q, k_pool, v_pool, pt, pos, page_size=ps)
    assert float(jnp.abs(o - oref).max()) < 2e-5


# -- the retired entry points warn and delegate ------------------------------

def test_legacy_entry_points_warn_and_delegate():
    """The API-redesign satellite: ``Engine.run()`` and
    ``FleetController.submit(Request)`` survive as shims that raise a
    DeprecationWarning and delegate to the blessed path (identical
    output), and the internal plumbing names pruned from
    ``repro.fleet.__all__`` stay importable for existing callers."""
    import repro.fleet as fleet_pkg
    from repro.core.attestation import TrustAuthority
    from repro.core.daemon import EDGE
    from repro.fleet import EngineHandle, FleetController

    eng = Engine(CFG, _params(), slots=1, max_len=32, seed=0)
    with pytest.warns(DeprecationWarning, match="Engine.run"):
        outs = eng.run([mk_req("legacy-run", np.arange(2, 6), max_new=4)])
    assert list(outs) == ["legacy-run"] and len(outs["legacy-run"]) == 4

    fleet = FleetController(
        [EngineHandle("e0", Engine(CFG, _params(), slots=1, max_len=32,
                                   seed=0), EDGE)],
        authority=TrustAuthority())
    req = mk_req("legacy-submit", np.arange(2, 6), max_new=4)
    with pytest.warns(DeprecationWarning, match="submit"):
        assert fleet.submit(req) is True     # legacy bool, not a ticket
    while not req.done:
        fleet.step()
    assert req.output == outs["legacy-run"]  # same engine geometry+seed

    for retired in ("WorkQueue", "EngineStats", "percentile",
                    "WindowedHistogram", "peek_slot_meta"):
        assert retired not in fleet_pkg.__all__
        assert hasattr(fleet_pkg, retired)   # plumbing stays importable
