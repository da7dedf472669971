"""The one traffic generator: reads a traffic file and makes requests.

A traffic file (``traffic/<name>.json``) holds only parameters:

    loop                 "closed" (sessions that wait for each reply) or
                         "open" (Poisson arrivals at ``rate_per_s``)
    sessions             closed loop: concurrent sessions
    think_s              closed loop: mean exponential think time
    stagger_s            closed loop: the sessions' first turns fall at
                         evenly spaced times over this many seconds
    rate_per_s           open loop: mean arrival rate
    ramp_s               seconds of traffic served before the measured
                         window opens (set-up), so that the window opens
                         in steady state
    tenants              tenants (0: none); session i belongs to i % tenants
    tenant_prefix_tokens a seeded system prompt per tenant, ahead of every
                         prompt of that tenant
    prompt_tokens        [[length, count], ...]: the prompt's own tokens
                         (after any tenant prefix), as counts per block
    output_tokens        [[length, count], ...]: greedy output tokens

Every seed gets the same work: the sizes and times (lengths, think times,
arrival gaps) are drawn from a fixed stream, one per session in a closed
loop, so each session makes the same sequence of turns on every seed;
the seed chooses only the tokens.  Lengths come in blocks that hold each
length exactly ``count`` times, shuffled within the block; think times and
inter-arrival gaps come in blocks of ten stratified quantiles of the
exponential, shuffled likewise.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

QUANTILES = 10
SIZES = 0x51235          # the fixed stream that sizes and times come from


@dataclass
class Req:
    rid: str
    prompt: np.ndarray
    max_new: int
    tenant: str = ""
    session: int = -1
    due: float = 0.0                 # seconds after the traffic starts
    t_due: float | None = None       # host clock, set by the harness
    t_submit: float | None = None
    t_tokens: list = field(default_factory=list)
    output: list = field(default_factory=list)   # the committed stream
    t_done: float | None = None
    ticket: object = None


class _Blocks:
    """Endless draws from shuffled balanced blocks."""

    def __init__(self, rng, values):
        self.rng, self.values, self.buf = rng, list(values), []

    def __call__(self):
        if not self.buf:
            self.buf = [self.values[i]
                        for i in self.rng.permutation(len(self.values))]
        return self.buf.pop()


def _mix(rng, pairs) -> _Blocks:
    return _Blocks(rng, [int(v) for v, n in pairs for _ in range(n)])


def _exponential(rng, mean: float) -> _Blocks:
    qs = [-mean * math.log(1 - (i + 0.5) / QUANTILES)
          for i in range(QUANTILES)]
    return _Blocks(rng, qs)


class _Stream:
    """Sizes and times of one session (or of the open loop's arrivals),
    and the seeded tokens of its prompts."""

    def __init__(self, spec: dict, seed: int, index: int, mean_gap: float):
        sizes = np.random.default_rng([SIZES, index])
        self.prompt_len = _mix(sizes, spec["prompt_tokens"])
        self.output_len = _mix(sizes, spec["output_tokens"])
        self.gap = _exponential(sizes, mean_gap)
        self.content = np.random.default_rng([seed, 1 + index])
        self.turns = 0


class Traffic:
    def __init__(self, spec: dict, seed: int, vocab: int, tag: str = ""):
        """``tag`` prefixes every request id (traffics served one after
        another by one fleet need ids of their own)."""
        self.spec, self.vocab, self.tag = spec, vocab, tag
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.ramp_s = float(spec.get("ramp_s", 0.0))
        self.tenants = [f"t{i}" for i in range(spec.get("tenants", 0))]
        self.rng = np.random.default_rng([seed, 0])   # prefixes, warm-up
        plen = spec.get("tenant_prefix_tokens", 0)
        self.prefix = {t: self.tokens(self.rng, plen) for t in self.tenants}
        mean = (spec["think_s"] if self.loop == "closed"
                else 1.0 / spec["rate_per_s"])
        n = spec["sessions"] if self.loop == "closed" else 1
        self.streams = [_Stream(spec, seed, i, mean) for i in range(n)]
        self.n_warm = 0

    # -- sizes ---------------------------------------------------------------
    @property
    def prefix_tokens(self) -> int:
        return self.spec.get("tenant_prefix_tokens", 0)

    def max_tokens(self) -> int:
        """The longest request: prefix + prompt + output tokens."""
        return (self.prefix_tokens
                + max(v for v, _ in self.spec["prompt_tokens"])
                + max(v for v, _ in self.spec["output_tokens"]))

    def tokens(self, rng, n: int) -> np.ndarray:
        return rng.integers(0, self.vocab, n).astype(np.int32)

    def _tenant(self, i: int) -> str:
        return self.tenants[i % len(self.tenants)] if self.tenants else ""

    def _with_prefix(self, tenant: str, own: np.ndarray) -> np.ndarray:
        return np.concatenate([self.prefix[tenant], own]) if tenant else own

    # -- set-up --------------------------------------------------------------
    def warm_phases(self, rows: int) -> list[list[Req]]:
        """Requests that warm every program the window can reach, one
        phase at a time: each tenant's bare prefix (filling the prefix
        cache), then for each prompt length one request per row the
        traffic can fill, all at once, so every such row runs that
        length's program.  A closed loop never holds more requests than
        it has sessions."""
        if self.loop == "closed":
            rows = min(rows, self.spec["sessions"])

        def warm(tenant, n):
            own = self.tokens(self.rng, n)
            self.n_warm += 1
            return Req(f"{self.tag}w{self.n_warm}",
                       self._with_prefix(tenant, own), 1, tenant)

        phases = [[warm(t, 0)] for t in self.tenants]
        for n, _ in self.spec["prompt_tokens"]:
            phases.append([warm(self._tenant(i), n) for i in range(rows)])
        return phases

    # -- the traffic ---------------------------------------------------------
    def _next(self, index: int) -> Req:
        """The next request of a session (closed) or arrival (open)."""
        s = self.streams[0 if self.loop == "open" else index]
        own = self.tokens(s.content, s.prompt_len())
        tenant = self._tenant(index)
        tag = self.tag + ("r" if self.loop == "open" else f"s{index}.")
        r = Req(f"{tag}{s.turns}", self._with_prefix(tenant, own),
                s.output_len(), tenant, index)
        s.turns += 1
        return r

    def initial(self, seconds: float) -> list[Req]:
        """Requests known before the traffic starts (``due`` counts from
        its start, the window opening ``ramp_s`` later): each session's
        first turn, or every open-loop arrival until the window closes."""
        out = []
        if self.loop == "closed":
            n = self.spec["sessions"]
            spread = self.spec.get("stagger_s", 0.0)
            for i in range(n):
                r = self._next(i)
                r.due = spread * (i + 0.5) / n + self.streams[i].gap()
                out.append(r)
            return out
        end = self.ramp_s + seconds
        t = self.streams[0].gap()
        i = 0
        while t < end:
            r = self._next(i)
            r.due = t
            out.append(r)
            t += self.streams[0].gap()
            i += 1
        return out

    def after(self, req: Req, t_done: float) -> list[Req]:
        """Closed loop: the session's next turn, a think time after its
        reply completed (``t_done`` in seconds from the traffic's start)."""
        if self.loop != "closed":
            return []
        r = self._next(req.session)
        r.due = t_done + self.streams[req.session].gap()
        return [r]
