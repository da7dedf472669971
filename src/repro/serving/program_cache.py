"""Process-wide compiled-program cache: spawn engines in milliseconds.

Every ``Engine``/``PagedEngine`` used to build private
``jax.jit(partial(...))`` closures in ``__init__``; each closure owns
its own trace/executable cache, so the *second* engine of a geometry
paid the full seconds-scale XLA compile again on its first call --
autoscale reaction was ~1 fleet step but time-to-first-useful-token was
seconds.  This module memoizes the jitted callables themselves, so
every engine of one key shares one set of programs and the compile is
paid once per process.

Key contract (see ROADMAP Contracts): two engines are served the SAME
jitted programs iff they agree on every element of

    (program family,            # "dense" | "paged"
     cfg identity,              # the ModelConfig object (by identity)
     mesh, partition rules,     # by identity
     batch geometry,            # slots/rows, max_len
     page geometry)             # page_size, pool pages (paged only)

Same key => same executable => the one-geometry-one-program contract's
bit-reproducibility carries across engines served from one entry: a
spawned engine decodes token-identically to the donor whose programs it
reuses, because it IS running the donor's programs.  Identity keys are
pinned (the entry holds strong references), so a recycled ``id()`` can
never alias two configs.

Each entry also tracks which program keys (``"decode"``,
``"prefill[slot=R,plen=N]"``, ...) have already executed once through
it -- i.e. are actually compiled -- so an engine's profile hook can
report a cache-served program as ``build_s ~ 0`` with a ``cache_hit``
annotation instead of claiming a fresh multi-second build
(time-to-useful spans stay honest).
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from typing import Any, Callable


@dataclass
class ProgramSet:
    """One cache entry: the shared jitted callables for one key, plus
    the program keys already executed (= compiled) through them."""
    key: tuple
    fns: dict[str, Any]              # program kind -> jitted callable
    compiled: set[str] = field(default_factory=set)
    served: int = 0                  # engines constructed from this entry
    pins: tuple = ()                 # strong refs: id()-keyed parts stay alive


def bind(fn: Callable, **bound) -> Callable:
    """``fn`` with ``bound`` keyword arguments fixed, under ``fn``'s own
    name.  XLA names a jitted program's module after the callable
    (``jit__paged_decode_step``); a bare ``partial`` has no name, and
    every program it makes would be ``jit__unknown``."""
    return functools.update_wrapper(functools.partial(fn, **bound), fn)


_lock = threading.Lock()
_sets: dict[tuple, ProgramSet] = {}


def program_key(family: str, cfg, mesh, rules, *, slots: int,
                max_len: int, page_size: int = 0, pages: int = 0) -> tuple:
    """The full sharing key.  ``cfg``/``mesh``/``rules`` key by identity
    (entries pin them, so ids stay unambiguous); the config name rides
    along for readable stats."""
    return (family, getattr(cfg, "name", None), id(cfg), id(mesh),
            id(rules), slots, max_len, page_size, pages)


def get_programs(family: str, cfg, mesh, rules, *, slots: int,
                 max_len: int, page_size: int = 0, pages: int = 0,
                 build: Callable[[], dict]) -> tuple[ProgramSet, bool]:
    """Fetch (or build-and-register) the program set for a key.

    Returns ``(set, cache_hit)``: ``cache_hit`` is True when an earlier
    engine already registered this key -- the caller reuses programs
    whose compiles (tracked in ``set.compiled``) are already paid."""
    key = program_key(family, cfg, mesh, rules, slots=slots,
                      max_len=max_len, page_size=page_size, pages=pages)
    with _lock:
        ps = _sets.get(key)
        if ps is not None:
            ps.served += 1
            return ps, True
        ps = ProgramSet(key=key, fns=build(), pins=(cfg, mesh, rules))
        _sets[key] = ps
        return ps, False


def clear():
    """Drop every entry (tests/benches: force the next engine of any
    geometry to rebuild -- and recompile -- its programs).  Live engines
    keep the program sets they already hold."""
    with _lock:
        _sets.clear()


def stats() -> dict:
    """Registry digest: entries, engines served beyond the first, and
    program keys compiled, per family."""
    with _lock:
        entries = list(_sets.values())
    return {
        "entries": len(entries),
        "cache_hits": sum(ps.served for ps in entries),
        "programs_compiled": sum(len(ps.compiled) for ps in entries),
    }
