"""Paged-KV serving engine: token-budget continuous batching.

Where the dense ``Engine`` pins one ``max_len`` cache row per request,
``PagedEngine`` carves its KV memory into fixed-size *pages* shared by
every batch row: a ``PageAllocator`` hands out pages, each request holds
a page table (logical position i lives at offset ``i % page_size`` of
page ``page_table[i // page_size]``), and admission is gated by the free
page budget rather than a free-slot count.  A short request reserves
only ``ceil((prompt + max_new) / page_size)`` pages, so an engine admits
and decodes far more concurrent requests than its dense slot count at
equal KV memory -- the classic vLLM block-table design, here behind the
Pallas ``paged_decode_attention`` kernel.

Migration ships *live pages only*: ``extract_slot`` gathers the
``ceil(position / page_size)`` pages a request has actually written
(plus its trimmed token prefix) into a v2 ``SlotSnapshot``, and
``inject_slot`` re-allocates a fresh reservation on the destination and
scatters the payload in.  Because pages are position-addressed, the v2
payload is geometry-free up to the page size: same page size + same
kernel program => bit-exact resume (the page-level contract that
replaces the dense path's slots=1 discipline -- see ROADMAP Contracts).

The decode batch width is still fixed (``rows``: the compiled program's
batch dimension), but rows are cheap -- they carry no KV memory of their
own -- so ``rows`` is sized for step throughput while the page pool is
sized for memory.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import make_paged_attn_cache
from repro.models.model import forward
from repro.serving.engine import (Engine, Request, SlotArrays, SlotSnapshot,
                                  request_from_dict, request_to_dict, span)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.program_cache import bind, get_programs
from repro.serving.sampling import sample


class PageAllocator:
    """LIFO free-list allocator over a fixed pool of KV pages.

    Tracks ownership so conservation is checkable at any point:
    ``len(free) + len(owners) == total`` always, no page is handed out
    twice, and freeing a page that is not owned raises.
    """

    def __init__(self, total: int):
        self.total = total
        self._free: list[int] = list(range(total - 1, -1, -1))
        self.owners: dict[int, str] = {}
        # extra invariant checks run by check() -- the prefix cache
        # registers its refcount/ownership audit here so every existing
        # allocator.check() call site also audits shared pages
        self.auditors: list = []

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self.owners)

    def alloc(self, n: int, owner: str) -> list[int] | None:
        """Hand out ``n`` pages to ``owner`` or None (never partial)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.owners[p] = owner
        return pages

    def free(self, pages: list[int]):
        for p in pages:
            if p not in self.owners:
                raise ValueError(f"freeing unowned page {p}")
            del self.owners[p]
            self._free.append(p)

    def retag(self, page: int, owner: str):
        """Transfer ownership of an allocated page (request -> prefix
        cache donation) without it ever appearing free."""
        if page not in self.owners:
            raise ValueError(f"retagging unowned page {page}")
        self.owners[page] = owner

    def check(self):
        """Conservation invariant; raises ``RuntimeError`` on violation.

        Real exceptions, not ``assert``: this is the load-bearing page
        ledger -- it must keep firing under ``python -O``."""
        if len(self._free) + len(self.owners) != self.total:
            raise RuntimeError(
                f"page ledger broken: {len(self._free)} free + "
                f"{len(self.owners)} owned != {self.total} total")
        if len(set(self._free)) != len(self._free):
            raise RuntimeError("free-list dup")
        if set(self._free) & set(self.owners):
            raise RuntimeError(
                f"pages both free and owned: "
                f"{sorted(set(self._free) & set(self.owners))}")
        for audit in self.auditors:
            audit()


@jax.tree_util.register_dataclass
@dataclass
class PagedEngineState:
    """Decode-loop state: like ``EngineState`` but caches are shared
    page pools and the per-row geometry lives in ``page_table``."""
    caches: list                     # [group][layer] {"attn": {k/v_pool}}
    page_table: jax.Array            # (B, NP) int32 page ids, -1 = unmapped
    tokens: jax.Array                # (B, max_len)
    positions: jax.Array             # (B,)
    last_token: jax.Array            # (B,)
    active: jax.Array                # (B,) bool
    rng: jax.Array                   # (B,)
    step_count: jax.Array            # ()
    temperature: jax.Array           # (B,)
    top_k: jax.Array                 # (B,)


class PagedEngine:
    """Drop-in engine with the dense ``Engine``'s duck-type surface
    (add_request/step/retire/extract_slot/inject_slot/rollback_slot/...)
    over a paged KV cache.  Attention-mixer models only (rwkv/mamba
    state is not paged); wide verify stays on the dense path."""

    paged = True

    def __init__(self, cfg: ModelConfig, params, *, page_size: int = 16,
                 pages: int | None = None, rows: int = 4,
                 max_len: int = 256, mesh=None, rules=None, seed: int = 0,
                 profile_hook=None, prefix_cache: bool = False,
                 shared_tenants: tuple = ()):
        assert all(ls.mixer in ("attn", "local")
                   for b in cfg.blocks for ls in b.layers) \
            and not cfg.cross_attention and not cfg.encoder_blocks, \
            "PagedEngine requires an attention-only decoder model"
        assert max_len % page_size == 0, (max_len, page_size)
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.np_pages = max_len // page_size     # page-table width NP
        # default pool: every row could hold a full max_len request --
        # same memory as the dense grid; smaller pools over-subscribe
        # rows, larger pools are useless (rows cap concurrency)
        self.pages = pages if pages is not None \
            else rows * self.np_pages
        self.rows = rows
        self.slots = rows                        # duck-type: load metric
        self.max_len = max_len
        self.mesh = mesh
        self.rules = rules
        self.requests: dict[int, Request] = {}
        self.allocator = PageAllocator(self.pages)
        self.state = self._fresh_state(seed)
        # shared process-wide programs: the pool size changes cache leaf
        # shapes, so `pages` is part of the sharing key
        self._programs, self.program_cache_hit = get_programs(
            "paged", cfg, mesh, rules, slots=rows, max_len=max_len,
            page_size=page_size, pages=self.pages,
            build=lambda: {
                "decode": jax.jit(bind(_paged_decode_step, cfg=cfg,
                                       mesh=mesh, rules=rules)),
                "prefill": jax.jit(bind(_paged_prefill, cfg=cfg,
                                        mesh=mesh, rules=rules),
                                   static_argnames=("slot", "plen")),
                "suffix": jax.jit(bind(_paged_suffix_prefill, cfg=cfg,
                                       mesh=mesh, rules=rules),
                                  static_argnames=("slot", "slen")),
            })
        self._decode_fn = self._programs.fns["decode"]
        self._prefill_fn = self._programs.fns["prefill"]
        self._suffix_fn = self._programs.fns["suffix"]
        self.profile_hook = profile_hook
        self._compiled: set[str] = set()
        # -- multi-tenant prefix sharing (opt-in) ---------------------------
        self.prefix_cache = None
        self._shared: dict[int, list] = {}   # row -> referenced PrefixNodes
        self.last_prefix_hit = 0             # tokens served shared, last admit
        if prefix_cache:
            self.prefix_cache = PrefixCache(
                self.allocator, page_size=page_size,
                cross_tenant=tuple(shared_tenants),
                token_bytes=self.kv_token_bytes)

    @property
    def kv_token_bytes(self) -> int:
        """KV bytes one token occupies across every layer's pools."""
        layers = sum(b.repeats * len(b.layers) for b in self.cfg.blocks)
        return (2 * layers * self.cfg.num_kv_heads * self.cfg.head_dim
                * jnp.dtype(self.cfg.dtype).itemsize)

    @property
    def page_bytes(self) -> int:
        return self.kv_token_bytes * self.page_size

    _profiled = Engine._profiled

    # -- state ------------------------------------------------------------
    def _fresh_state(self, seed: int) -> PagedEngineState:
        B = self.rows
        caches = []
        for block in self.cfg.blocks:
            layers = []
            for _ in block.layers:
                one = {"attn": make_paged_attn_cache(
                    self.cfg, self.pages, self.page_size)}
                layers.append(jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (block.repeats,) + a.shape).copy(), one))
            caches.append(layers)
        return PagedEngineState(
            caches=caches,
            page_table=jnp.full((B, self.np_pages), -1, jnp.int32),
            tokens=jnp.zeros((B, self.max_len), jnp.int32),
            positions=jnp.zeros((B,), jnp.int32),
            last_token=jnp.zeros((B,), jnp.int32),
            active=jnp.zeros((B,), bool),
            rng=jax.vmap(jax.random.key)(jnp.arange(seed, seed + B,
                                                    dtype=jnp.uint32)),
            step_count=jnp.zeros((), jnp.int32),
            temperature=jnp.zeros((B,), jnp.float32),
            top_k=jnp.zeros((B,), jnp.int32),
        )

    # -- capacity ----------------------------------------------------------
    @property
    def free_slots(self) -> list[int]:
        return [i for i in range(self.rows) if i not in self.requests]

    def _pages_for(self, need_tokens: int) -> int:
        return -(-need_tokens // self.page_size)

    def _evictable_pages(self) -> int:
        """Refcount-0 prefix-cache pages: reclaimed on demand at admit,
        so they honestly count as free capacity."""
        return (self.prefix_cache.evictable_pages()
                if self.prefix_cache is not None else 0)

    def can_admit(self, need_tokens: int, *, cached_tokens: int = 0) -> bool:
        """``cached_tokens`` (page-aligned, from ``prefix_hit_tokens``)
        discounts the page reservation only -- the row must still hold
        the full stream, so the ``max_len`` bound stays unreduced."""
        need_pages = (self._pages_for(need_tokens)
                      - cached_tokens // self.page_size)
        return (bool(self.free_slots)
                and need_tokens <= self.max_len
                and need_pages
                <= self.allocator.free_pages + self._evictable_pages())

    def admissible(self, need_tokens: int) -> bool:
        return (need_tokens <= self.max_len
                and self._pages_for(need_tokens) <= self.allocator.total)

    def prefix_hit_tokens(self, tenant: str, tokens) -> int:
        """Full-page-aligned cached coverage of ``tokens`` for
        ``tenant``: that many prefill tokens would be served from shared
        pages (and as many pages skipped from the reservation).  The
        router's session-affinity and capacity term."""
        if self.prefix_cache is None or tokens is None or not len(tokens):
            return 0
        return self.prefix_cache.hit_tokens(tenant, tokens)

    def prefix_hit_tokens_hashed(self, tenant: str, hashed) -> int:
        """``prefix_hit_tokens`` over a router-precomputed
        ``HashedPrefix`` -- one hashing pass serves every engine."""
        if self.prefix_cache is None or hashed is None \
                or not len(hashed.tokens):
            return 0
        return self.prefix_cache.hit_tokens_hashed(tenant, hashed)

    @property
    def free_token_budget(self) -> int:
        if not self.free_slots:
            return 0
        return ((self.allocator.free_pages + self._evictable_pages())
                * self.page_size)

    # -- request lifecycle --------------------------------------------------
    def _row_pages(self, row: int) -> list[int]:
        # waits for every program already dispatched that writes the
        # page table (a suffix scan, a decode step)
        with span("engine.sync", site="page_table"):
            pt = np.asarray(self.state.page_table[row])
        return [int(p) for p in pt if p >= 0]

    def add_request(self, req: Request, *,
                    committed: list[int] | None = None) -> bool:
        """Admit iff a decode row is free AND the reservation fits the
        free page budget -- reserving up front means an admitted request
        can never deadlock mid-decode waiting for pages.

        With a prefix cache armed, the reservation is charged *honestly
        small*: the longest cached prefix chain is referenced in place
        (one refcount per shared page, zero new pages), a cached partial
        tail is COW-forked into one private page, and only the uncovered
        suffix + decode budget allocates fresh pages.  Only that suffix
        is forwarded -- a full hit skips the prefill program entirely.
        """
        free = self.free_slots
        if not free:
            return False
        with span("engine.admit") as sp:
            return self._admit(req, free[0], committed, sp)

    def _admit(self, req: Request, row: int, committed, sp) -> bool:
        need = len(req.prompt) + req.max_new_tokens
        assert need <= self.max_len
        prefix = np.asarray(req.prompt, np.int32)
        extra = list(committed) if committed else []
        if extra:
            prefix = np.concatenate(
                [prefix, np.asarray(extra, np.int32)])
        plen = len(prefix)
        cache = self.prefix_cache
        tenant = getattr(req, "tenant", "")
        with span("prefix.match"):
            full_nodes, tail, hit = (cache.match(tenant, prefix)
                                     if cache is not None
                                     else ([], None, 0))
        n_ref = len(full_nodes)
        need_priv = self._pages_for(need) - n_ref
        pages = self.allocator.alloc(need_priv, req.rid)
        if pages is None and cache is not None:
            # refcount-0 shared pages are part of the advertised budget:
            # reclaim LRU-first and retry before refusing
            cache.reclaim(need_priv - self.allocator.free_pages)
            pages = self.allocator.alloc(need_priv, req.rid)
        if pages is None:
            return False
        # counted once admitted: a refused admission's span has none
        sp.set_metadata(prompt=plen, cached=hit)
        req.slot = row
        self.requests[row] = req
        if extra:
            req.output[:] = extra
        if full_nodes:
            cache.acquire(full_nodes)
        self._shared[row] = list(full_nodes)
        pt_row = np.full((self.np_pages,), -1, np.int32)
        pt_row[:n_ref] = [n.page for n in full_nodes]
        pt_row[n_ref:n_ref + len(pages)] = pages
        s = self.state
        self.state = dataclasses.replace(
            s,
            page_table=s.page_table.at[row].set(jnp.asarray(pt_row)),
            temperature=s.temperature.at[row].set(req.temperature),
            top_k=s.top_k.at[row].set(req.top_k))
        if tail is not None and hit > n_ref * self.page_size:
            # COW fork: the block containing the first decode position
            # will be written in place, so the cached tail page is
            # copied into this row's first private page, never shared
            self._copy_page(tail.page, pages[0])
        self.last_prefix_hit = hit
        if hit >= plen:
            # full hit: every prompt token's KV is already in this row's
            # page table (shared chain + COW tail) -- no forward at all
            self._warm_start(row, prefix)
        elif hit == 0:
            prompt = jnp.asarray(prefix, jnp.int32)[None]
            with span("engine.prefill"):
                self.state = self._profiled(
                    f"prefill[slot={row},plen={plen}]",
                    lambda: self._prefill_fn(self.params, self.state,
                                             prompt, slot=row, plen=plen))
        else:
            # suffix-only prefill: seed the covered region, then forward
            # just the uncovered tokens through the decode-mode program
            # (prefill-mode attention never reads the page pools, so the
            # suffix must attend to the shared prefix via the kernel)
            self._warm_start(row, prefix[:hit])
            suffix = jnp.asarray(prefix[hit:], jnp.int32)[None]
            slen = plen - hit
            with span("engine.suffix_prefill", tokens=slen):
                self.state = self._profiled(
                    f"suffix[slot={row},slen={slen}]",
                    lambda: self._suffix_fn(self.params, self.state,
                                            suffix, slot=row, slen=slen))
        if cache is not None:
            self._donate(row, tenant, prefix, hit)
            cache.account(hit)
        return True

    def _warm_start(self, row: int, covered: np.ndarray):
        """Seed a row as if ``covered`` had just been prefilled: tokens
        written, position past the covered region, last token primed.
        The KV for the region must already sit in the row's page table
        (shared prefix chain + COW'd tail)."""
        s = self.state
        cov = jnp.asarray(covered, jnp.int32)[None]
        self.state = dataclasses.replace(
            s,
            tokens=jax.lax.dynamic_update_slice(
                s.tokens, cov, (jnp.int32(row), jnp.int32(0))),
            positions=s.positions.at[row].set(len(covered)),
            last_token=s.last_token.at[row].set(int(covered[-1])),
            active=s.active.at[row].set(True))

    def _copy_page(self, src: int, dst: int):
        """Copy one physical page across every layer's pools (the COW
        fork and the tail-donation copy)."""
        def cp(layer):
            a = layer["attn"]
            return {"attn": {
                "k_pool": a["k_pool"].at[:, dst].set(a["k_pool"][:, src]),
                "v_pool": a["v_pool"].at[:, dst].set(a["v_pool"][:, src]),
            }}
        s = self.state
        self.state = dataclasses.replace(
            s, caches=[[cp(l) for l in grp] for grp in s.caches])

    def _copy_page_from(self, donor: PagedEngine, src: int, dst: int):
        """Copy one physical page from ``donor``'s pools into this
        engine's (cross-engine prefix pre-warm).  Pool layer structure
        matches by precondition: ``prewarm_chains`` only pairs engines
        of one config/page geometry."""
        ds = donor.state

        def cp(layer, dlayer):
            a, b = layer["attn"], dlayer["attn"]
            return {"attn": {
                "k_pool": a["k_pool"].at[:, dst].set(
                    b["k_pool"][:, src].astype(a["k_pool"].dtype)),
                "v_pool": a["v_pool"].at[:, dst].set(
                    b["v_pool"][:, src].astype(a["v_pool"].dtype)),
            }}

        s = self.state
        self.state = dataclasses.replace(
            s, caches=[[cp(l, dl) for l, dl in zip(grp, dgrp)]
                       for grp, dgrp in zip(s.caches, ds.caches)])

    def prewarm_chains(self, donor: PagedEngine, *, top_k: int = 4) -> dict:
        """Pre-warm this engine's prefix cache from a same-geometry
        donor: graft the donor's hottest refcount>0 full-block chains
        (most recently touched first, at most ``top_k`` chains) by
        copying each page into a locally allocated one.  Spawned and
        promoted engines come up warm in *cache*, not just in code.

        Best-effort with a *loud skip*: the report says how many chains
        and pages landed and why it stopped (``skipped``), it never
        raises -- prewarm is an optimization, not a correctness step.
        """
        report = {"chains": 0, "pages": 0, "skipped": None}
        mine, theirs = self.prefix_cache, donor.prefix_cache
        if mine is None or theirs is None:
            report["skipped"] = "no prefix cache on donor or target"
            return report
        if (donor.page_size != self.page_size
                or donor.cfg.name != self.cfg.name):
            report["skipped"] = (
                f"geometry mismatch: donor {donor.cfg.name}"
                f"/ps={donor.page_size} vs {self.cfg.name}"
                f"/ps={self.page_size}")
            return report
        # hottest chain := most recently touched hot (refcount>0) node;
        # the chain is that node's ancestry, grafted root-first
        hot = sorted((n for n in theirs.nodes.values() if n.refs > 0),
                     key=lambda n: n.stamp, reverse=True)
        planned: list = []
        chains = 0
        for leaf in hot:
            if chains >= top_k:
                break
            chain = []
            node, seen = leaf, {n.key for n in planned}
            while node is not None:
                if node.key in seen or node.key in mine.nodes:
                    break            # ancestry already planned/local
                chain.append(node)
                node = theirs.nodes.get(node.parent) \
                    if node.parent is not None else None
            if not chain:
                continue
            planned.extend(reversed(chain))
            chains += 1
        for node in planned:
            pages = self.allocator.alloc(1, f"prewarm:{node.key}")
            if pages is None:
                report["skipped"] = (
                    f"page budget exhausted after {report['pages']} of "
                    f"{len(planned)} pages")
                break
            self._copy_page_from(donor, node.page, pages[0])
            if mine.graft(node, pages[0]) is None:
                self.allocator.free(pages)
                continue
            report["pages"] += 1
        report["chains"] = chains
        return report

    def _donate(self, row: int, tenant: str, prefix: np.ndarray, hit: int):
        """Publish this row's freshly prefilled prompt blocks into the
        cache: full blocks transfer page ownership in place (the row
        keeps a reference), the partial tail is donated as a copy (the
        row's own tail page is about to be written by decode)."""
        cache, ps = self.prefix_cache, self.page_size
        nodes = self._shared[row]
        with span("prefix.donate"):
            row_pages = self._row_pages(row)
            for d in range(len(nodes), len(prefix) // ps):
                node = cache.adopt(tenant, prefix, d, row_pages[d])
                if node is None:
                    # a peer cached this block since we matched; keep
                    # our private page (swapping pages mid-request would
                    # break the row's bit-exactness) and stop extending
                    # the chain
                    return
                cache.acquire([node])
                nodes.append(node)
            if len(prefix) % ps and hit < len(prefix):
                d = len(prefix) // ps
                cache.adopt_tail(
                    tenant, prefix,
                    lambda dst: self._copy_page(row_pages[d], dst))

    def step(self, *, auto_retire: bool = True) -> dict[str, int]:
        if not self.requests:
            return {}
        # keys: the positions this step's attention reads, summed over
        # the live rows: each row's prompt and output, and the token fed
        live = lambda: [r for r in self.requests.values()  # noqa: E731
                        if not r.done]
        with span("engine.decode", rows=lambda: len(live()),
                  keys=lambda: sum(len(r.prompt) + len(r.output) + 1
                                   for r in live())):
            self.state, toks = self._profiled(
                "decode", lambda: self._decode_fn(self.params, self.state))
            with span("engine.sync", site="tokens"):
                toks = np.asarray(toks)
            emitted = {}
            for row, req in list(self.requests.items()):
                if req.done:
                    continue
                t = int(toks[row])
                req.output.append(t)
                emitted[req.rid] = t
                if auto_retire and len(req.output) >= req.max_new_tokens:
                    req.done = True
                    self.retire(row)
        return emitted

    def retire(self, row: int):
        self.requests.pop(row, None)
        pages = self._row_pages(row)
        nodes = self._shared.pop(row, None)
        if nodes:
            # shared pages occupy the leading page-table entries: drop
            # the references (the cache frees them only at refcount-0
            # eviction) and free just this row's private pages
            self.prefix_cache.release(nodes)
            pages = pages[len(nodes):]
        if pages:
            self.allocator.free(pages)
        s = self.state
        self.state = dataclasses.replace(
            s,
            page_table=s.page_table.at[row].set(-1),
            active=s.active.at[row].set(False))

    def check(self):
        """Engine-level conservation audit: allocator invariants (incl.
        the prefix cache's ownership/refcount auditor), the page ledger
        (used == row-private + cache-held), and exact refcounts against
        the live rows' shared chains."""
        self.allocator.check()
        if not set(self._shared) <= set(self.requests):
            raise RuntimeError(
                f"shared-chain rows without live requests: "
                f"{sorted(set(self._shared) - set(self.requests))}")
        private = sum(len(self._row_pages(r)) - len(self._shared.get(r, ()))
                      for r in self.requests)
        held = self.prefix_cache.pages_held \
            if self.prefix_cache is not None else 0
        if self.allocator.used_pages != private + held:
            raise RuntimeError(
                f"page ledger broken: used={self.allocator.used_pages} != "
                f"private={private} + cache-held={held}")
        if self.prefix_cache is not None:
            self.prefix_cache.check(self._shared.values())

    # -- per-slot live migration (v2: live pages; v3: suffix only) ----------
    def extract_slot(self, slot: int, *, keep: bool = False,
                     suffix_only: bool = False) -> SlotSnapshot:
        """Detach one request shipping only its live pages.

        The payload's cache leaves are (R, n_live, page_size, KV, Dh)
        where ``n_live = ceil(position / page_size)`` -- position-ordered
        pages, free of this engine's pool indices -- plus the token
        prefix trimmed to the live region.  Wire version 2.

        ``suffix_only`` (wire version 3) drops the shared prefix-chain
        pages from the payload and ships their chain *hashes* instead
        (``snap.prefix``): a destination whose prefix cache holds the
        chain re-references those pages locally and only the private
        suffix pages cross the wire.  Callers must verify the
        destination holds the chain first (``prefix_cache.has_chain``)
        -- injecting v3 into a cache that misses raises loudly.
        """
        req = self.requests[slot]
        pos = int(self.state.positions[slot])
        ps = self.page_size
        n_live = max(1, -(-pos // ps))
        row_pages = self._row_pages(slot)
        shared = self._shared.get(slot, [])
        n_skip = 0
        prefix_meta = None
        if suffix_only:
            assert shared, "suffix_only extract needs a shared chain"
            n_skip = min(len(shared), n_live)
            prefix_meta = {
                "tenant": getattr(req, "tenant", ""),
                "chain": [n.key for n in shared[:n_skip]],
                "len": n_skip * ps,
            }
        live = jnp.asarray(
            np.asarray(row_pages[n_skip:n_live], np.int32))

        def gather(layer):
            a = layer["attn"]
            return {"attn": {"k": a["k_pool"][:, live],
                             "v": a["v_pool"][:, live]}}

        arrays = SlotArrays(
            caches=[[gather(l) for l in grp]
                    for grp in self.state.caches],
            tokens=self.state.tokens[slot, :n_live * ps],
            position=self.state.positions[slot],
            last_token=self.state.last_token[slot],
            rng=self.state.rng[slot],
            temperature=self.state.temperature[slot],
            top_k=self.state.top_k[slot],
        )
        snap = SlotSnapshot(
            arrays=arrays,
            request=request_to_dict(req),
            config_name=self.cfg.name,
            step=int(self.state.step_count),
            version=3 if suffix_only else 2,
            page_size=ps,
            prefix=prefix_meta,
        )
        if not keep:
            self.retire(slot)
        return snap

    def inject_slot(self, snap: SlotSnapshot,
                    slot: int | None = None) -> Request:
        """Resume a v2 snapshot: allocate a fresh full reservation here,
        scatter the live pages into it, pad the token prefix out to this
        engine's max_len.  Page ids are engine-local, so the donor's and
        destination's pools never need to line up -- only the page size
        and kernel program do (the page-level contract)."""
        assert self.cfg.name == snap.config_name, \
            f"config mismatch: {self.cfg.name} != {snap.config_name}"
        if snap.version not in (2, 3):
            raise ValueError(
                f"PagedEngine.inject_slot needs a v2/v3 (paged) "
                f"snapshot, got v{snap.version}; route dense blobs "
                f"through lossy re-prefill")
        if snap.page_size != self.page_size:
            raise ValueError(
                f"page_size mismatch: blob {snap.page_size} != engine "
                f"{self.page_size} (cross-geometry moves are lossy)")
        a = snap.arrays
        req = request_from_dict(snap.request)
        nodes = []
        if snap.version == 3:
            # suffix-only blob: the prefix chain's pages must already
            # live in this engine's cache -- re-reference, don't re-wire
            if self.prefix_cache is None:
                raise ValueError(
                    f"v3 (suffix-only) blob for {req.rid!r} but this "
                    "engine has no prefix cache; the sender must fall "
                    "back to full v2")
            nodes = self.prefix_cache.lookup_chain(snap.prefix["chain"])
            if nodes is None:
                raise ValueError(
                    f"v3 (suffix-only) blob for {req.rid!r}: destination "
                    f"prefix cache is missing the {len(snap.prefix['chain'])}"
                    f"-block chain; the sender must fall back to full v2")
        n_sh = len(nodes)
        need = len(req.prompt) + req.max_new_tokens
        assert need <= self.max_len, (need, self.max_len)
        n_live = a.caches[0][0]["attn"]["k"].shape[1]
        pages = self.allocator.alloc(
            max(self._pages_for(need) - n_sh, n_live), req.rid)
        if pages is None and self.prefix_cache is not None:
            self.prefix_cache.reclaim(
                max(self._pages_for(need) - n_sh, n_live)
                - self.allocator.free_pages)
            pages = self.allocator.alloc(
                max(self._pages_for(need) - n_sh, n_live), req.rid)
        if pages is None:
            raise RuntimeError(
                f"no free page budget to inject {req.rid!r} into")
        if slot is None:
            free = self.free_slots
            if not free:
                raise RuntimeError(
                    f"no free row to inject {req.rid!r} into")
            slot = free[0]
        if slot in self.requests:
            raise RuntimeError(f"row {slot} busy")
        live = jnp.asarray(np.asarray(pages[:n_live], np.int32))

        def scatter(pool_layer, pay_layer):
            p, q = pool_layer["attn"], pay_layer["attn"]
            return {"attn": {
                "k_pool": p["k_pool"].at[:, live].set(
                    q["k"].astype(p["k_pool"].dtype)),
                "v_pool": p["v_pool"].at[:, live].set(
                    q["v"].astype(p["v_pool"].dtype)),
            }}

        s = self.state
        caches = [[scatter(l, pl_) for l, pl_ in zip(grp, pgrp)]
                  for grp, pgrp in zip(s.caches, a.caches)]
        if nodes:
            self.prefix_cache.acquire(nodes)
            self._shared[slot] = list(nodes)
        pt_row = np.full((self.np_pages,), -1, np.int32)
        pt_row[:n_sh] = [n.page for n in nodes]
        pt_row[n_sh:n_sh + len(pages)] = pages
        tokens = jnp.zeros((self.max_len,), jnp.int32).at[
            :a.tokens.shape[0]].set(a.tokens)
        impl = str(jax.random.key_impl(s.rng))
        rng = jax.random.wrap_key_data(
            jax.random.key_data(s.rng).at[slot].set(
                jax.random.key_data(a.rng)), impl=impl)
        self.state = dataclasses.replace(
            s,
            caches=caches,
            page_table=s.page_table.at[slot].set(jnp.asarray(pt_row)),
            tokens=s.tokens.at[slot].set(tokens),
            positions=s.positions.at[slot].set(a.position),
            last_token=s.last_token.at[slot].set(a.last_token),
            active=s.active.at[slot].set(True),
            rng=rng,
            temperature=s.temperature.at[slot].set(a.temperature),
            top_k=s.top_k.at[slot].set(a.top_k))
        req.slot = slot
        self.requests[slot] = req
        return req

    def slot_like(self):
        """Structure template for v2 wire deserialization.  Only the
        pytree *structure* matters (deserialize_tree takes shapes and
        dtypes from the blob -- the live-page axis varies per snapshot),
        so leaves are placeholder ShapeDtypeStructs."""
        ps, KV, Dh = (self.page_size, self.cfg.num_kv_heads,
                      self.cfg.head_dim)
        dt = jnp.dtype(self.cfg.dtype)

        def layer(repeats):
            sds = jax.ShapeDtypeStruct((repeats, 1, ps, KV, Dh), dt)
            return {"attn": {"k": sds, "v": sds}}

        return SlotArrays(
            caches=[[layer(block.repeats) for _ in block.layers]
                    for block in self.cfg.blocks],
            tokens=jax.ShapeDtypeStruct((ps,), jnp.int32),
            position=jax.ShapeDtypeStruct((), jnp.int32),
            last_token=jax.ShapeDtypeStruct((), jnp.int32),
            rng=jax.eval_shape(lambda: jax.random.key(0)),
            temperature=jax.ShapeDtypeStruct((), jnp.float32),
            top_k=jax.ShapeDtypeStruct((), jnp.int32),
        )

    # -- speculative tier surface -------------------------------------------
    @property
    def supports_wide_verify(self) -> bool:
        return False                 # single-token decode program only

    def _force_slot_token(self, slot: int, token: int):
        s = self.state
        t = jnp.int32(token)
        self.state = dataclasses.replace(
            s,
            tokens=s.tokens.at[slot, s.positions[slot] - 1].set(t),
            last_token=s.last_token.at[slot].set(t))

    def rollback_slot(self, slot: int, drafted: int, accepted: int,
                      commit_token: int | None = None):
        """Identical contract to the dense engine: stale page contents
        past the rewound position stay behind but are invisible (the
        attend mask cuts at ``position``) and are rewritten in place."""
        s = self.state
        p0 = int(s.positions[slot]) - drafted
        assert p0 >= 0, (slot, drafted)
        if commit_token is None:
            new_pos = p0
            last = s.tokens[slot, max(p0 - 1, 0)]
            tokens = s.tokens
        else:
            assert 0 <= accepted <= drafted
            new_pos = p0 + accepted + 1
            last = jnp.int32(commit_token)
            tokens = s.tokens.at[slot, new_pos - 1].set(commit_token)
        self.state = dataclasses.replace(
            s,
            tokens=tokens,
            positions=s.positions.at[slot].set(new_pos),
            last_token=s.last_token.at[slot].set(last))


# ---------------------------------------------------------------------------
# jitted step functions
# ---------------------------------------------------------------------------

def _weave(caches, pt):
    """Broadcast the master page table (B, NP) into every attn layer's
    cache dict (stacked (R, B, NP)) so `attention_apply` can address the
    shared pools per batch row."""
    out = []
    for grp in caches:
        layers = []
        for layer in grp:
            a = dict(layer["attn"])
            R = a["k_pool"].shape[0]
            a["page_table"] = jnp.broadcast_to(pt[None], (R,) + pt.shape)
            layers.append({"attn": a})
        out.append(layers)
    return out


def _paged_prefill(params, state: PagedEngineState, prompt, *, slot: int,
                   plen: int, cfg, mesh, rules):
    """Prefill one row.  The pools are shared, so unlike the dense path
    there is no per-slot cache slice/scatter-back: the batch=1 forward
    writes straight into the row's reserved pages."""
    pt_row = jax.lax.dynamic_slice_in_dim(state.page_table, slot, 1, 0)
    caches = _weave(state.caches, pt_row)
    _, caches, _ = forward(
        params, {"tokens": prompt}, cfg=cfg, mode="prefill",
        caches=caches, mesh=mesh, rules=rules)
    tokens = jax.lax.dynamic_update_slice(
        state.tokens, prompt, (jnp.int32(slot), jnp.int32(0)))
    return dataclasses.replace(
        state,
        caches=caches,
        tokens=tokens,
        positions=state.positions.at[slot].set(plen),
        last_token=state.last_token.at[slot].set(prompt[0, -1]),
        active=state.active.at[slot].set(True),
    )


def _paged_suffix_prefill(params, state: PagedEngineState, suffix, *,
                          slot: int, slen: int, cfg, mesh, rules):
    """Prefill the uncovered suffix of a warm row, one token per decode
    step.

    The prefill program computes attention over only the tokens it is
    fed (``attention_causal`` never reads the page pools), so a suffix
    that must attend to a *cached* prefix has to go through the
    decode-mode kernel path: each suffix token is forwarded at its
    absolute position, reads the shared prefix pages through the row's
    page table, and writes its own KV into the row's private pages.
    The row's position must already sit at the covered-prefix length
    (``_warm_start``); logits are discarded -- this is KV construction,
    not sampling -- and the row finishes exactly like a cold prefill:
    position at plen, last prompt token primed for the first decode.
    """
    pt_row = jax.lax.dynamic_slice_in_dim(state.page_table, slot, 1, 0)
    start = state.positions[slot]

    def body(caches, i):
        tok = jax.lax.dynamic_slice(suffix, (0, i), (1, 1))
        woven = _weave(caches, pt_row)
        _, caches, _ = forward(
            params, {"tokens": tok}, cfg=cfg, mode="decode",
            caches=woven, positions=(start + i)[None, None],
            mesh=mesh, rules=rules)
        return caches, None

    caches, _ = jax.lax.scan(body, state.caches, jnp.arange(slen))
    tokens = jax.lax.dynamic_update_slice(
        state.tokens, suffix, (jnp.int32(slot), start))
    return dataclasses.replace(
        state,
        caches=caches,
        tokens=tokens,
        positions=state.positions.at[slot].set(start + slen),
        last_token=state.last_token.at[slot].set(suffix[0, -1]),
        active=state.active.at[slot].set(True),
    )


def _paged_decode_step(params, state: PagedEngineState, *, cfg, mesh,
                       rules):
    """One decode step for every active row.

    Inactive rows decode on garbage like the dense path, but their
    masking is structural rather than copy-on-write: their page-table
    rows are swapped to -1, so their pool writes drop (out-of-bounds
    sentinel) and their attends see only dead pages.  No cache
    select/where is needed -- the pools only ever receive writes from
    active rows."""
    pt_eff = jnp.where(state.active[:, None], state.page_table, -1)
    caches = _weave(state.caches, pt_eff)
    pos = state.positions[:, None]
    logits, caches, _ = forward(
        params, {"tokens": state.last_token[:, None]}, cfg=cfg,
        mode="decode", caches=caches, positions=pos,
        mesh=mesh, rules=rules)
    toks, rng = sample(logits[:, 0], state.rng, cfg,
                       temperature=state.temperature, top_k=state.top_k)
    toks = jnp.where(state.active, toks, 0)
    tokens = jax.vmap(
        lambda row, t, p: jax.lax.dynamic_update_index_in_dim(row, t, p, 0)
    )(state.tokens, toks, state.positions)
    return dataclasses.replace(
        state,
        caches=caches,
        tokens=jnp.where(state.active[:, None], tokens, state.tokens),
        positions=jnp.where(state.active, state.positions + 1,
                            state.positions),
        last_token=jnp.where(state.active, toks, state.last_token),
        rng=rng,
        step_count=state.step_count + 1,
    ), toks
