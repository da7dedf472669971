"""Serving engine: slot-based continuous batching over jitted
prefill/decode steps.

The engine owns a fixed number of request *slots* (the batch dimension
of the decode step).  Requests attach to free slots, prefill fills the
slot's cache region, and every ``step()`` advances all active slots one
token.  All device state lives in one ``EngineState`` pytree -- which is
exactly the *agent workspace* the MVVM layer snapshots, attests,
migrates and replicates (core/workspace.py wraps it).

Stable points (paper §7.3): the boundary between two ``step()`` calls is
the WASM "checkpoint ip" analogue -- every piece of cross-step state is
explicit in ``EngineState``, so a snapshot taken between steps restores
bit-identically anywhere.
"""

from __future__ import annotations

import dataclasses
import inspect
import time
import warnings
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.configs.base import ModelConfig
from repro.models.model import forward, make_cache, vocab_mask_logits
from repro.serving.program_cache import bind, get_programs
from repro.serving.sampling import policy_probs, sample


def _call_profile_hook(hook, key: str, wall_s: float, *,
                       cache_hit: bool = False):
    """Invoke a profile hook, passing ``cache_hit`` only to hooks that
    can take it (a ``cache_hit`` parameter or ``**kwargs``); legacy
    two-positional hooks keep working unchanged."""
    try:
        params = inspect.signature(hook).parameters
    except (TypeError, ValueError):
        hook(key, wall_s)
        return
    if "cache_hit" in params or any(
            p.kind is inspect.Parameter.VAR_KEYWORD
            for p in params.values()):
        hook(key, wall_s, cache_hit=cache_hit)
    else:
        hook(key, wall_s)


def span(name: str, **counts):
    """A host span called ``name`` on the profiler's own timeline, the
    one the device ops are on, with ``counts`` as its stats (more can be
    added before it closes with ``.set_metadata``).  Counts are host
    values the caller already holds, or functions of none that work
    them out: one read from a device array would add the very wait these
    spans are there to show.  Counts are attached, and those functions
    called, only while a trace is being recorded; with none running a
    span costs about a microsecond and computes nothing."""
    if not counts or not jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.TraceAnnotation(
        name, **{k: v() if callable(v) else v for k, v in counts.items()})


@jax.tree_util.register_dataclass
@dataclass
class EngineState:
    """Everything the decode loop carries across steps (the workspace)."""
    caches: list                     # model KV / ssm state
    tokens: jax.Array                # (B, max_len) generated+prompt tokens
    positions: jax.Array             # (B,) next position to write
    last_token: jax.Array            # (B,) most recent token per slot
    active: jax.Array                # (B,) bool slot in use
    rng: jax.Array                   # (B,) per-slot sampling keys
    step_count: jax.Array            # () total decode steps executed
    temperature: jax.Array           # (B,) per-slot sampling temperature
    top_k: jax.Array                 # (B,) per-slot top-k (0 = full vocab)


@dataclass
class Request:
    rid: str
    prompt: np.ndarray
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    sensitivity: str = "public"      # public | personal | confidential
    priority: int = 0                # higher dispatches first / preempts
    deadline: Optional[float] = None  # absolute fleet-clock expiry
    quality_floor: float = 0.0       # min tier quality this request accepts
    tenant: str = ""                 # prefix-cache namespace ("" = default)
    done: bool = False
    output: list = field(default_factory=list)
    slot: int = -1


def request_to_dict(req: Request) -> dict:
    """Wire form of request metadata (workspace / slot snapshots)."""
    return {
        "rid": req.rid, "prompt": np.asarray(req.prompt).tolist(),
        "max_new_tokens": req.max_new_tokens,
        "temperature": req.temperature, "top_k": req.top_k,
        "sensitivity": req.sensitivity, "priority": req.priority,
        "deadline": req.deadline, "quality_floor": req.quality_floor,
        "tenant": req.tenant,
        "output": list(req.output),
        "slot": req.slot, "done": req.done,
    }


def request_from_dict(d: dict) -> Request:
    req = Request(rid=d["rid"], prompt=np.asarray(d["prompt"]),
                  max_new_tokens=d["max_new_tokens"],
                  temperature=d["temperature"], top_k=d["top_k"],
                  sensitivity=d["sensitivity"],
                  priority=d.get("priority", 0),
                  deadline=d.get("deadline"),
                  quality_floor=d.get("quality_floor", 0.0),
                  tenant=d.get("tenant", ""))
    req.output = list(d["output"])
    req.slot = d["slot"]
    req.done = d["done"]
    return req


@jax.tree_util.register_dataclass
@dataclass
class SlotArrays:
    """One slot's share of ``EngineState`` (batch dim sliced away)."""
    caches: list                     # per-leaf (R, ...) cache rows
    tokens: jax.Array                # (max_len,)
    position: jax.Array              # ()
    last_token: jax.Array            # ()
    rng: jax.Array                   # () sampling key
    temperature: jax.Array           # ()
    top_k: jax.Array                 # ()


@dataclass
class SlotSnapshot:
    """A single in-flight request, detached from its engine: the unit of
    per-request live migration (one slot leaves a draining engine and
    resumes -- bit-identically -- in any free slot of a peer engine)."""
    arrays: SlotArrays
    request: dict                    # request_to_dict form
    config_name: str
    step: int                        # donor step_count at extraction
    trace: Optional[dict] = None     # tracer wire context: the migrate
    #                                  hop span opened on the donor rides
    #                                  the blob so the destination closes
    #                                  that exact span (pack_slot meta)
    version: int = 1                 # wire format: 1 = dense cache rows,
    #                                  2 = live pages only (paged engine),
    #                                  3 = suffix pages + prefix-chain
    #                                      hashes (shared-prefix moves)
    page_size: int = 0               # v2/v3 only: tokens per KV page
    prefix: Optional[dict] = None    # v3 only: {"tenant", "chain", "len"}
    #                                  -- the shared chain the payload
    #                                  rides on; the destination must
    #                                  hold these blocks in its prefix
    #                                  cache or inject fails loudly

    @property
    def rid(self) -> str:
        return self.request["rid"]

    @property
    def sensitivity(self) -> str:
        return self.request["sensitivity"]

    @property
    def remaining_tokens(self) -> int:
        return self.request["max_new_tokens"] - len(self.request["output"])


class Engine:
    """Single-replica serving engine for one model on one mesh."""

    paged = False                    # dense (slots, max_len) KV grid
    page_size = 0                    # >0 only on paged engines

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 256, mesh=None, rules=None, seed: int = 0,
                 profile_hook=None):
        self.cfg = cfg
        if mesh is not None:
            # weights are laid out by the mesh's rules (e.g. heads over
            # "model"), never left whole on the default device
            params = jax.device_put(
                params, shd.param_shardings(cfg, mesh, rules))
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.mesh = mesh
        self.rules = rules
        self.requests: dict[int, Request] = {}
        self.state = self._fresh_state(seed)
        # jitted programs come from the process-wide program cache: every
        # engine of one (cfg, mesh, rules, slots, max_len) key shares one
        # set of callables, so a spawned engine reuses the donor
        # geometry's compiled prefill/decode/probs/verify with zero
        # rebuild (``program_cache_hit`` records the provenance)
        self._programs, self.program_cache_hit = get_programs(
            "dense", cfg, mesh, rules, slots=slots, max_len=max_len,
            build=lambda: {
                "decode": jax.jit(bind(_decode_step, cfg=cfg, mesh=mesh,
                                       rules=rules)),
                "prefill": jax.jit(bind(_prefill, cfg=cfg, mesh=mesh,
                                        rules=rules),
                                   static_argnames=("slot", "plen")),
                "verify": jax.jit(bind(_verify_window, cfg=cfg,
                                       mesh=mesh, rules=rules)),
                "probs": jax.jit(bind(_decode_step_probs, cfg=cfg,
                                      mesh=mesh, rules=rules)),
            })
        self._decode_fn = self._programs.fns["decode"]
        self._prefill_fn = self._programs.fns["prefill"]
        self._verify_fn = self._programs.fns["verify"]
        # jit programs compile on first invocation per program key; the
        # hook (``profile_hook(key, wall_s)``) receives the wall time of
        # exactly that first call -- compile-dominated when the program
        # cache missed, ~0 when a peer engine already compiled it (the
        # hook is then told ``cache_hit=True`` when it can take it) --
        # so the fleet tracer can attribute program builds to spawn
        # spans without claiming phantom compiles
        self.profile_hook = profile_hook
        self._compiled: set[str] = set()

    def _profiled(self, key: str, fn):
        """Run ``fn``; if this is the first invocation of program ``key``
        on this engine, time it to completion (``block_until_ready``)
        and report to ``profile_hook``.  ``key`` names the program and
        every static argument it is compiled for
        (``prefill[slot=0,plen=6]``), so each key is one executable.
        Warm keys run untouched, and a key is marked warm even with no
        hook attached so a hook wired in later never reports an
        already-compiled program as a build.  A key another engine
        already ran through the shared program set is reported as a
        cache hit: the wall time is the (tiny) first dispatch, not a
        compile.  The first run is an ``engine.first_run`` span."""
        if key in self._compiled:
            return fn()
        self._compiled.add(key)
        shared = self._programs.compiled
        warm = key in shared
        shared.add(key)
        with span("engine.first_run", program=key.split("[")[0], key=key):
            if self.profile_hook is None:
                return fn()
            t0 = time.perf_counter()
            out = fn()
            with span("engine.sync", site="first_run"):
                jax.block_until_ready(out)
            _call_profile_hook(self.profile_hook, key,
                               time.perf_counter() - t0, cache_hit=warm)
        return out

    # -- state ------------------------------------------------------------
    def _fresh_state(self, seed: int) -> EngineState:
        B = self.slots
        return EngineState(
            caches=make_cache(self.cfg, B, self.max_len),
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

    # -- request lifecycle --------------------------------------------------
    @property
    def free_slots(self) -> list[int]:
        return [i for i in range(self.slots) if i not in self.requests]

    # -- capacity (token-budget admission surface) -------------------------
    # The fleet layer gates placement through these three instead of
    # counting free slots, so dense and paged engines answer the same
    # questions: can this request start *now*, could it *ever* fit here,
    # and how many KV tokens of headroom remain.
    def can_admit(self, need_tokens: int) -> bool:
        """True if a request needing ``need_tokens`` KV slots (prompt +
        max_new) can be admitted right now."""
        return bool(self.free_slots) and need_tokens <= self.max_len

    def admissible(self, need_tokens: int) -> bool:
        """True if such a request could ever fit on this engine (ignoring
        current occupancy)."""
        return need_tokens <= self.max_len

    @property
    def free_token_budget(self) -> int:
        """KV-token headroom: dense engines pin a full max_len row per
        request regardless of its length."""
        return len(self.free_slots) * self.max_len

    def add_request(self, req: Request, *,
                    committed: list[int] | None = None) -> bool:
        """Attach a request to a free slot and prefill it.

        ``committed`` is the lossy cross-tier restore path: a request
        migrating between tiers with *distinct weights* cannot carry its
        cache rows (they were computed by a different model), so the
        destination re-prefills prompt + the committed token stream and
        decode continues from there -- token history preserved, device
        state rebuilt.  The committed tokens become the request's output
        prefix."""
        free = self.free_slots
        if not free:
            return False
        slot = free[0]
        req.slot = slot
        self.requests[slot] = req
        prefix = np.asarray(req.prompt, np.int32)
        if committed:
            req.output[:] = list(committed)
            prefix = np.concatenate(
                [prefix, np.asarray(committed, np.int32)])
        plen = len(prefix)
        assert len(req.prompt) + req.max_new_tokens <= self.max_len
        self.state = dataclasses.replace(
            self.state,
            temperature=self.state.temperature.at[slot].set(req.temperature),
            top_k=self.state.top_k.at[slot].set(req.top_k))
        prompt = jnp.asarray(prefix, jnp.int32)[None]
        self.state = self._profiled(
            f"prefill[slot={slot},plen={plen}]",
            lambda: self._prefill_fn(self.params, self.state, prompt,
                                     slot=slot, plen=plen))
        return True

    def step(self, *, auto_retire: bool = True) -> dict[str, int]:
        """One batched decode step; returns {rid: token} emitted.

        ``auto_retire=False`` keeps slots open past ``max_new_tokens``:
        a speculative drafting tier appends *uncommitted* tokens to
        ``req.output`` and must retire/roll back explicitly after the
        verifier rules on them."""
        if not self.requests:
            return {}
        self.state, toks = self._profiled(
            "decode", lambda: self._decode_fn(self.params, self.state))
        toks = np.asarray(toks)
        emitted = {}
        for slot, req in list(self.requests.items()):
            if req.done:
                continue
            t = int(toks[slot])
            req.output.append(t)
            emitted[req.rid] = t
            if auto_retire and len(req.output) >= req.max_new_tokens:
                req.done = True
                self.retire(slot)
        return emitted

    def step_probs(self, *, auto_retire: bool = True) \
            -> tuple[dict[str, int], Optional[np.ndarray]]:
        """One batched decode step that also returns, per slot, the full
        sampling distribution the emitted token was drawn from
        (``(B, padded_vocab)`` float32; one-hot argmax for greedy
        slots).

        This is the draft side of distribution-level speculative
        acceptance: a draft tier with *distinct weights* must ship its
        proposal distributions q so the verifier can run the standard
        accept/reject rule against the target's p -- token equality is
        meaningless across weights.  The probs program shares the decode
        program's structure but compiles separately, so its knife-edge
        greedy picks may differ from ``step()``'s (the usual
        one-geometry-one-program reproducibility rule applies *within*
        either program, not across them)."""
        if not self.requests:
            return {}, None
        self.state, toks, probs = self._profiled(
            "decode_probs",
            lambda: self._decode_probs(self.params, self.state))
        toks = np.asarray(toks)
        emitted = {}
        for slot, req in list(self.requests.items()):
            if req.done:
                continue
            t = int(toks[slot])
            req.output.append(t)
            emitted[req.rid] = t
            if auto_retire and len(req.output) >= req.max_new_tokens:
                req.done = True
                self.retire(slot)
        return emitted, np.asarray(probs)

    @property
    def _decode_probs(self):
        return self._programs.fns["probs"]

    def retire(self, slot: int):
        self.requests.pop(slot, None)
        self.state = _deactivate(self.state, slot)

    # -- per-slot live migration (fleet layer) ------------------------------
    def extract_slot(self, slot: int, *, keep: bool = False) -> SlotSnapshot:
        """Detach one in-flight request as a ``SlotSnapshot``.

        The snapshot packs the slot's cache rows, token tail, position,
        sampling rng and per-slot policy -- everything needed to resume
        this request bit-identically in *any* free slot of a compatible
        engine.  Unless ``keep``, the slot is drained (request removed,
        slot deactivated) as in a live migration's departure side;
        ``keep=True`` is the shadow-checkpoint (replica sync) form."""
        req = self.requests[slot]
        snap = SlotSnapshot(
            arrays=_slot_arrays(self.state, slot),
            request=request_to_dict(req),
            config_name=self.cfg.name,
            step=int(self.state.step_count))
        if not keep:
            self.retire(slot)
        return snap

    def inject_slot(self, snap: SlotSnapshot,
                    slot: int | None = None) -> Request:
        """Resume a migrated request in a free slot (any index).

        The donor's slot index is irrelevant: rows are written into
        whatever slot is free here, and decode continues bit-identically
        because every piece of cross-step state rides in the snapshot."""
        # exact match: cache-row geometry must be identical, so the loose
        # tiny/full family check workspace.attach uses is not enough here
        assert self.cfg.name == snap.config_name, \
            f"config mismatch: {self.cfg.name} != {snap.config_name}"
        a = snap.arrays
        assert a.tokens.shape[-1] == self.max_len, \
            f"max_len mismatch: {a.tokens.shape[-1]} != {self.max_len}"
        if slot is None:
            free = self.free_slots
            assert free, "no free slot to inject into"
            slot = free[0]
        assert slot not in self.requests, f"slot {slot} busy"
        s = self.state
        caches = jax.tree.map(lambda full, row: full.at[:, slot].set(row),
                              s.caches, a.caches)
        impl = str(jax.random.key_impl(s.rng))
        rng = jax.random.wrap_key_data(
            jax.random.key_data(s.rng).at[slot].set(
                jax.random.key_data(a.rng)), impl=impl)
        self.state = dataclasses.replace(
            s,
            caches=caches,
            tokens=s.tokens.at[slot].set(a.tokens),
            positions=s.positions.at[slot].set(a.position),
            last_token=s.last_token.at[slot].set(a.last_token),
            active=s.active.at[slot].set(True),
            rng=rng,
            temperature=s.temperature.at[slot].set(a.temperature),
            top_k=s.top_k.at[slot].set(a.top_k))
        req = request_from_dict(snap.request)
        req.slot = slot
        self.requests[slot] = req
        return req

    def slot_like(self):
        """abstract SlotArrays (shapes/dtypes) for wire deserialization."""
        return jax.eval_shape(lambda: _slot_arrays(self.state, 0))

    # -- speculative verify tier (fleet layer) ------------------------------
    @property
    def supports_wide_verify(self) -> bool:
        """Wide (multi-query) verify windows need every mixer to be
        cache-attention; recurrent mixers step one token at a time."""
        return (not self.cfg.cross_attention
                and not self.cfg.encoder_blocks
                and all(ls.mixer in ("attn", "local")
                        for b in self.cfg.blocks for ls in b.layers))

    def verify_slots(self, drafts: dict[int, list[int]], *,
                     width: int | None = None) -> dict[int, tuple[int, int]]:
        """Teacher-forced batch verification of drafted tails.

        ``drafts[slot]`` holds the tokens a draft tier proposed for that
        slot since its last committed position.  ONE wide forward pass
        (gamma+1 queries per slot, every query causally masked at its own
        position) scores all windows of all verifying slots together --
        the batched analogue of core/speculation's one-wide-matmul target
        pass.  Greedy acceptance: a draft token is accepted iff it equals
        the target argmax given the accepted prefix; the first rejection
        cuts the tail and the target's own argmax at the cut (or the
        bonus token after a fully-accepted window) is committed instead.

        Numerics caveat: the wide program's matmul shapes differ from the
        one-token decode program's, so XLA rounds differently and greedy
        choices on knife-edge logits can deviate from a pure decode run
        of this same engine (production speculative-decoding stacks share
        this property).  ``verify_slots_stepwise`` trades the wide pass
        for bit-exactness when token-identical output is the contract.

        Slot state advances to the committed prefix (tokens, position,
        last_token); rows the rejected suffix dirtied stay masked by
        ``abs_pos`` until decode naturally rewrites them in place.
        Returns {slot: (n_accepted, correction_token | None)} -- the
        correction token is present exactly when the window was cut
        short (None = fully accepted, nothing to splice)."""
        assert drafts, "nothing to verify"
        g = width if width is not None else max(map(len, drafts.values()))
        B = self.slots
        arr = np.zeros((B, g), np.int32)
        cnt = np.zeros((B,), np.int32)
        mask = np.zeros((B,), bool)
        pos = np.asarray(self.state.positions)
        for slot, toks in drafts.items():
            assert slot in self.requests, f"slot {slot} not in use"
            assert 0 < len(toks) <= g, (slot, len(toks), g)
            assert pos[slot] + g + 1 <= self.max_len, \
                f"verify window overruns max_len at slot {slot}"
            arr[slot, :len(toks)] = toks
            cnt[slot] = len(toks)
            mask[slot] = True
        self.state, n_acc, commit = self._profiled(
            "verify_wide",
            lambda: self._verify_fn(self.params, self.state,
                                    jnp.asarray(arr), jnp.asarray(cnt),
                                    jnp.asarray(mask)))
        n_acc, commit = np.asarray(n_acc), np.asarray(commit)
        return {slot: (int(n_acc[slot]),
                       None if commit[slot] < 0 else int(commit[slot]))
                for slot in drafts}

    def verify_slots_stepwise(self, drafts: dict[int, list[int]]) \
            -> dict[int, tuple[int, int]]:
        """Bit-exact verification: teacher-force the engine's OWN jitted
        decode program over each drafted tail.

        Every burst step runs ``_decode_fn`` -- the exact compiled
        program a pure run of this engine uses -- so the greedy token it
        emits *is* the pure-run token: acceptance (token equality) and
        corrections are bit-exact by construction, not by numerical
        accident.  Slots that finish (first rejection, or tail
        exhausted) are mask-deactivated for the rest of the burst, the
        same masking a partially-idle batch uses; all verifying slots
        advance together, so the burst costs max(len(tail)) steps
        regardless of how many slots verify.

        Same contract as ``verify_slots``: the slot ends at its
        committed prefix and the return maps slot -> (n_accepted,
        correction_token | None)."""
        assert drafts, "nothing to verify"
        saved_active = self.state.active
        burst = np.zeros((self.slots,), bool)
        for slot, toks in drafts.items():
            assert slot in self.requests, f"slot {slot} not in use"
            assert toks, f"empty draft tail for slot {slot}"
            burst[slot] = True
        self.state = dataclasses.replace(
            self.state, active=jnp.asarray(burst) & saved_active)
        results: dict[int, tuple[int, int | None]] = {}
        pending = {slot: list(toks) for slot, toks in drafts.items()}
        step = 0
        while pending:
            self.state, toks = self._decode_fn(self.params, self.state)
            toks = np.asarray(toks)
            for slot in list(pending):
                t = int(toks[slot])
                if t != pending[slot][step]:          # rejection: t is
                    results[slot] = (step, t)         # the correction,
                elif step + 1 == len(pending[slot]):  # already committed
                    results[slot] = (step + 1, None)
                else:
                    continue
                del pending[slot]
                self.state = dataclasses.replace(
                    self.state,
                    active=self.state.active.at[slot].set(False))
            step += 1
        self.state = dataclasses.replace(self.state, active=saved_active)
        return results

    def verify_slots_distribution(self, drafts: dict[int, list[int]],
                                  draft_probs: dict[int, np.ndarray], *,
                                  rng) -> dict[int, tuple[int, int]]:
        """Distribution-level verification: standard speculative-sampling
        accept/reject (Leviathan et al.) of drafted tails against this
        engine's own next-token distributions.

        The token-equality modes (``verify_slots`` / ``_stepwise``)
        assume draft and target share weights, so an accepted token IS
        the target's token.  A draft tier with *distinct* weights (an
        int8 or small-model quality tier) can never win that test on
        purpose; the correct contract is distributional: accept draft
        token ``d_i`` with probability ``min(1, p(d_i)/q(d_i))`` and
        resample the cut position from ``max(p - q, 0)`` -- the
        committed stream is then distributed exactly as a pure run of
        THIS engine, whatever the drafter proposed (greedy requests
        reduce to argmax agreement: one-hot p and q).

        ``draft_probs[slot]`` is the ``(len(tail), padded_vocab)`` stack
        of proposal distributions captured by the drafter's
        ``step_probs``; ``rng`` drives acceptance + resampling (split
        per slot).  Scoring teacher-forces the drafted tokens through
        the engine's probs program (each step's sampled token is
        overwritten by the draft token before it is consumed), then the
        slot rewinds to its committed prefix exactly like the other
        verify modes.  A fully-accepted window commits only the drafts
        -- the bonus token is refused for the same KV-gap reason as
        ``_verify_window``.  Returns {slot: (n_accepted,
        commit_token | None)}."""
        from repro.kernels import ops as kops
        assert drafts, "nothing to verify"
        saved_active = self.state.active
        burst = np.zeros((self.slots,), bool)
        for slot, toks in drafts.items():
            assert slot in self.requests, f"slot {slot} not in use"
            assert toks, f"empty draft tail for slot {slot}"
            assert len(draft_probs[slot]) == len(toks), slot
            assert int(self.state.positions[slot]) + len(toks) + 1 \
                <= self.max_len, \
                f"scoring window overruns max_len at slot {slot}"
            burst[slot] = True
        self.state = dataclasses.replace(
            self.state, active=jnp.asarray(burst) & saved_active)
        p_rows: dict[int, list] = {slot: [] for slot in drafts}
        live = dict(drafts)
        step = 0
        while live:
            self.state, _, probs = self._decode_probs(self.params,
                                                      self.state)
            probs = np.asarray(probs)
            for slot in list(live):
                p_rows[slot].append(probs[slot])
                if step < len(live[slot]):
                    # teacher-force: the NEXT step must consume the
                    # draft token, not the engine's own sample
                    self._force_slot_token(slot, live[slot][step])
                else:                 # bonus row collected: done
                    del live[slot]
                    self.state = dataclasses.replace(
                        self.state,
                        active=self.state.active.at[slot].set(False))
            step += 1
        self.state = dataclasses.replace(self.state, active=saved_active)

        results: dict[int, tuple[int, int | None]] = {}
        for slot in sorted(drafts):
            tail = drafts[slot]
            q = jnp.asarray(np.asarray(draft_probs[slot], np.float32))
            p = jnp.asarray(np.stack(p_rows[slot]).astype(np.float32))
            n_acc, nxt = kops.spec_verify(
                jnp.asarray(tail, jnp.int32), q, p,
                jax.random.fold_in(rng, slot))
            n_acc = int(n_acc)
            if n_acc >= len(tail):
                # fully accepted: rewind past the scored bonus row only
                # (no bonus token -- see _verify_window's KV-gap note)
                self.rollback_slot(slot, 1, 0, None)
                results[slot] = (len(tail), None)
            else:
                self.rollback_slot(slot, len(tail) + 1, n_acc, int(nxt))
                results[slot] = (n_acc, int(nxt))
        return results

    def _force_slot_token(self, slot: int, token: int):
        """Overwrite the token a decode step just emitted for ``slot``
        (teacher-forcing: the next step consumes ``token`` instead)."""
        s = self.state
        t = jnp.int32(token)
        self.state = dataclasses.replace(
            s,
            tokens=s.tokens.at[slot, s.positions[slot] - 1].set(t),
            last_token=s.last_token.at[slot].set(t))

    def rollback_slot(self, slot: int, drafted: int, accepted: int,
                      commit_token: int | None = None):
        """Rewind a slot's speculative tail to the verified prefix.

        Of the last ``drafted`` uncommitted tokens keep ``accepted`` and
        splice ``commit_token`` (the verifier's correction or bonus) in
        as the next committed token; ``commit_token=None`` drops the
        whole tail (e.g. the verify tier vanished mid-round).  Cache rows
        the dropped suffix wrote stay behind but are invisible -- their
        ``abs_pos`` exceeds the rewound position -- and decode rewrites
        each row in place before it ever becomes attendable again."""
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

    def run(self, reqs: list[Request]) -> dict[str, list[int]]:
        """Deprecated: drive ``add_request``/``step`` directly (or submit
        ``RequestSpec``s to a ``FleetController``)."""
        warnings.warn(
            "Engine.run() is deprecated; drive add_request()/step() "
            "directly or submit RequestSpecs to a FleetController",
            DeprecationWarning, stacklevel=2)
        return self._run(reqs)

    def _run(self, reqs: list[Request]) -> dict[str, list[int]]:
        pending = list(reqs)
        outputs = {}
        while pending or self.requests:
            while pending and self.add_request(pending[0]):
                outputs[pending[0].rid] = pending[0].output
                pending.pop(0)
            if self.requests:
                self.step()
        return outputs


# ---------------------------------------------------------------------------
# jitted step functions
# ---------------------------------------------------------------------------

def _prefill(params, state: EngineState, prompt, *, slot: int, plen: int,
             cfg, mesh, rules):
    """Prefill one slot.  The model runs with batch=1 on the slot's cache
    rows; results are scattered back into the engine state."""
    sub_caches = jax.tree.map(lambda a: a[:, slot:slot + 1], state.caches)
    logits, sub_caches, _ = forward(
        params, {"tokens": prompt}, cfg=cfg, mode="prefill",
        caches=sub_caches, mesh=mesh, rules=rules)
    caches = jax.tree.map(
        lambda full, sub: jax.lax.dynamic_update_index_in_dim(
            full, sub[:, 0], slot, 1),
        state.caches, sub_caches)
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


def _decode_step(params, state: EngineState, *, cfg, mesh, rules):
    """One decode step for every active slot (inactive slots compute but
    their state is masked out -- the static-shape batching standard).
    Sampling policy is per-slot: mixed-temperature batches read their
    temperature/top_k rows out of the state."""
    pos = state.positions[:, None]
    logits, caches, _ = forward(
        params, {"tokens": state.last_token[:, None]}, cfg=cfg,
        mode="decode", caches=state.caches, positions=pos,
        mesh=mesh, rules=rules)
    toks, rng = sample(logits[:, 0], state.rng, cfg,
                       temperature=state.temperature, top_k=state.top_k)
    toks = jnp.where(state.active, toks, 0)
    # only active slots advance
    caches = jax.tree.map(
        lambda new, old: jnp.where(
            _bcast(state.active, new.ndim, new.shape), new, old),
        caches, state.caches)
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


def _decode_step_probs(params, state: EngineState, *, cfg, mesh, rules):
    """``_decode_step`` that additionally returns each slot's full
    sampling distribution (B, padded_vocab) -- the law the emitted token
    was drawn from (one-hot argmax for greedy slots).  The speculative
    distribution-acceptance path needs these: the drafter ships its
    proposal distributions q, the verifier scores target distributions
    p, and the accept/reject rule runs on the p/q ratio."""
    pos = state.positions[:, None]
    logits, caches, _ = forward(
        params, {"tokens": state.last_token[:, None]}, cfg=cfg,
        mode="decode", caches=state.caches, positions=pos,
        mesh=mesh, rules=rules)
    probs = policy_probs(logits[:, 0], cfg, temperature=state.temperature,
                         top_k=state.top_k)
    toks, rng = sample(logits[:, 0], state.rng, cfg,
                       temperature=state.temperature, top_k=state.top_k)
    toks = jnp.where(state.active, toks, 0)
    caches = jax.tree.map(
        lambda new, old: jnp.where(
            _bcast(state.active, new.ndim, new.shape), new, old),
        caches, state.caches)
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
    ), toks, probs


def _verify_window(params, state: EngineState, drafts, counts, verify,
                   *, cfg, mesh, rules):
    """Score gamma drafted tokens per slot in ONE forward pass and commit
    the greedy-accepted prefix (+ correction/bonus token).

    drafts: (B, g) proposed tokens (row b valid up to counts[b]);
    verify: (B,) bool -- slots actually verifying this round.  The
    window's inputs are (last_token, d_1 .. d_g) at absolute positions
    (p0 .. p0+g): exactly the tokens a plain decode loop would have fed,
    so greedy acceptance reproduces the verify engine's own output
    bit-exactly.  Non-verifying slots compute on garbage but their state
    (caches included) is masked back, mirroring ``_decode_step``."""
    B, g = drafts.shape
    W = g + 1
    inputs = jnp.concatenate([state.last_token[:, None], drafts], axis=1)
    pos = state.positions[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
    logits, caches, _ = forward(
        params, {"tokens": inputs}, cfg=cfg, mode="decode",
        caches=state.caches, positions=pos, mesh=mesh, rules=rules)
    # greedy target choice, identical to sample()'s temperature-0 path
    greedy = jnp.argmax(
        vocab_mask_logits(logits, cfg).astype(jnp.float32),
        -1).astype(jnp.int32)                              # (B, W)
    j = jnp.arange(g, dtype=jnp.int32)[None]
    match = (greedy[:, :g] == drafts) & (j < counts[:, None])
    n_acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    commit = jnp.take_along_axis(greedy, n_acc[:, None], axis=1)[:, 0]

    # Correction tokens are committed only on a REJECTION.  A fully-
    # accepted window must not take the Leviathan bonus token: neither
    # tier has processed the window's last draft as an *input* yet, so
    # advancing past it would leave a permanent hole in the KV rows at
    # its position.  Committing exactly the accepted drafts keeps both
    # tiers' caches gap-free (the next window's first input rewrites the
    # boundary row).
    full = n_acc == counts                                 # (B,) bool
    n_commit = jnp.where(full, n_acc, n_acc + 1)
    last_acc = jnp.take_along_axis(
        drafts, jnp.maximum(n_acc - 1, 0)[:, None], axis=1)[:, 0]
    new_last = jnp.where(full, last_acc, commit)

    # committed window: accepted drafts, then (on rejection) the
    # correction token, then whatever the token rows already held
    old_win = jax.vmap(
        lambda row, p: jax.lax.dynamic_slice(row, (p,), (W,))
    )(state.tokens, state.positions)
    drafts_w = jnp.concatenate(
        [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)
    jw = jnp.arange(W, dtype=jnp.int32)[None]
    new_win = jnp.where(jw < n_acc[:, None], drafts_w,
                        jnp.where((jw == n_acc[:, None]) & ~full[:, None],
                                  commit[:, None], old_win))
    tokens = jax.vmap(
        lambda row, win, p: jax.lax.dynamic_update_slice(row, win, (p,))
    )(state.tokens, new_win, state.positions)

    caches = jax.tree.map(
        lambda new, old: jnp.where(
            _bcast(verify, new.ndim, new.shape), new, old),
        caches, state.caches)
    state = dataclasses.replace(
        state,
        caches=caches,
        tokens=jnp.where(verify[:, None], tokens, state.tokens),
        positions=jnp.where(verify, state.positions + n_commit,
                            state.positions),
        last_token=jnp.where(verify, new_last, state.last_token),
        step_count=state.step_count + 1,
    )
    return state, n_acc, jnp.where(full, -1, commit)


def _bcast(active, ndim, shape):
    """Broadcast (B,) active mask against a cache leaf.

    Cache leaves are stacked (R, B, ...): the batch dim is axis 1; plain
    per-layer leaves have batch at axis 0."""
    if ndim >= 2 and shape[0] != active.shape[0]:
        mask = active[None, :]
        return mask.reshape((1, -1) + (1,) * (ndim - 2))
    return active.reshape((-1,) + (1,) * (ndim - 1))


def _deactivate(state: EngineState, slot: int) -> EngineState:
    return dataclasses.replace(state,
                               active=state.active.at[slot].set(False))


def _slot_arrays(state: EngineState, slot: int) -> SlotArrays:
    """Slice one slot out of the batched state (cache batch dim is axis 1,
    matching ``_prefill``'s scatter)."""
    return SlotArrays(
        caches=jax.tree.map(lambda a: a[:, slot], state.caches),
        tokens=state.tokens[slot],
        position=state.positions[slot],
        last_token=state.last_token[slot],
        rng=state.rng[slot],
        temperature=state.temperature[slot],
        top_k=state.top_k[slot],
    )
