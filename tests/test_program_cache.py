"""The process-wide compiled-program cache (serving/program_cache.py):
key discrimination (family, config identity, geometry, page pool),
shared ``ProgramSet`` identity across engines, honest ``cache_hit``
reporting through old- and new-style profile hooks, and ``clear()``
forcing a rebuild.  The spawn-path integration (promotion, standby
warm-up) lives in tests/test_fleet_autoscale.py.
"""

import jax
import numpy as np

from repro.configs import get
from repro.configs.tiny import make_tiny
from repro.serving import program_cache as pc
from repro.serving.engine import Engine, Request
from repro.serving.paged import PagedEngine

CFG = make_tiny(get("llama-1.5b"))
PARAMS = None


def _params():
    global PARAMS
    if PARAMS is None:
        from repro.models.init import init_params
        PARAMS = init_params(CFG, jax.random.key(0))
    return PARAMS


def test_program_key_discriminates_geometry_and_family():
    k = lambda **kw: pc.program_key(  # noqa: E731
        kw.pop("family", "dense"), CFG, None, None,
        **{"slots": 2, "max_len": 64, **kw})
    assert k() == k()
    assert k() != k(slots=4)
    assert k() != k(max_len=128)
    assert k() != k(family="paged")
    # the paged pool size changes cache leaf shapes: part of the key
    assert k(family="paged", page_size=8, pages=6) \
        != k(family="paged", page_size=8, pages=12)
    other = make_tiny(get("llama-1.5b"))       # equal content, new object
    assert k() != pc.program_key("dense", other, None, None,
                                 slots=2, max_len=64)


def test_get_programs_shares_one_set_and_counts():
    calls = []

    def build():
        calls.append(1)
        return {"decode": object()}

    key_kw = dict(slots=3, max_len=32)
    ps1, hit1 = pc.get_programs("dense", CFG, None, None,
                                **key_kw, build=build)
    ps2, hit2 = pc.get_programs("dense", CFG, None, None,
                                **key_kw, build=build)
    assert not hit1 and hit2
    assert ps1 is ps2 and len(calls) == 1
    assert ps1.served == 1                     # engines beyond the first
    assert ps1.pins[0] is CFG                  # identity keys stay alive
    st = pc.stats()
    assert st["entries"] >= 1


def test_engines_share_programs_and_clear_forces_rebuild():
    e1 = Engine(CFG, _params(), slots=1, max_len=48, seed=0)
    e2 = Engine(CFG, _params(), slots=1, max_len=48, seed=1)
    assert e2.program_cache_hit
    assert e2._programs is e1._programs
    assert e2._decode_fn is e1._decode_fn
    pc.clear()
    e3 = Engine(CFG, _params(), slots=1, max_len=48, seed=2)
    assert not e3.program_cache_hit            # rebuilt after clear()
    assert e3._programs is not e1._programs
    # live engines keep the set they were constructed with
    assert e1._decode_fn is e2._decode_fn


def test_paged_engines_share_by_pool_geometry():
    p1 = PagedEngine(CFG, _params(), page_size=8, pages=6, rows=2,
                     max_len=48, seed=0)
    p2 = PagedEngine(CFG, _params(), page_size=8, pages=6, rows=2,
                     max_len=48, seed=1)
    assert p2.program_cache_hit and p2._programs is p1._programs
    assert p2._suffix_fn is p1._suffix_fn
    bigger = PagedEngine(CFG, _params(), page_size=8, pages=12, rows=2,
                         max_len=48, seed=2)
    assert bigger._programs is not p1._programs


def test_profile_hook_reports_cache_hits_honestly():
    """The first engine's hook sees cache_hit=False per program; a
    sibling engine's hook sees cache_hit=True for programs the first
    already executed -- and a legacy 2-arg hook keeps working."""
    pc.clear()
    seen1, seen2, legacy = [], [], []
    e1 = Engine(CFG, _params(), slots=1, max_len=48, seed=0,
                profile_hook=lambda key, wall_s, cache_hit=False:
                seen1.append((key, cache_hit)))
    e2 = Engine(CFG, _params(), slots=1, max_len=48, seed=1,
                profile_hook=lambda key, wall_s, cache_hit=False:
                seen2.append((key, cache_hit)))
    e3 = Engine(CFG, _params(), slots=1, max_len=48, seed=2,
                profile_hook=lambda key, wall_s: legacy.append(key))

    def run(eng, rid):
        req = Request(rid, np.arange(2, 8), max_new_tokens=2)
        eng.add_request(req)
        while not req.done:
            eng.step()
        return req.output

    outs = [run(e, f"r{i}") for i, e in enumerate((e1, e2, e3))]
    assert outs[0] == outs[1] == outs[2]       # same executables
    first = {}                                 # e1 pays each compile once
    for k, hit in seen1:
        first.setdefault(k, hit)
    assert first == {"prefill[slot=0,plen=6]": False, "decode": False}
    assert seen2 and all(hit for _, hit in seen2)   # e2 rides e1's programs
    assert set(legacy) == {"prefill[slot=0,plen=6]", "decode"}  # no crash
