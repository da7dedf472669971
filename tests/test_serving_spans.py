"""Spans of the paged serving path on the profiler's own timeline.

A tiny ``PagedEngine`` with its prefix cache armed serves behind a
``FleetController`` under ``jax.profiler.trace``: a cold admission of a
two-page prompt, then two admissions that extend it by the same number
of tokens (suffix prefills on rows 0 and 1), each decoding a few steps.
The trace is read back with the benchmark's own reduction
(``perfbench/trace_reduce.py``), and its spans and their stats are held
to what the engine did.
"""

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.configs.tiny import make_tiny
from repro.core.daemon import CLOUD
from repro.fleet import EngineHandle, FleetController, RequestSpec
from repro.models.init import init_params
from repro.serving.engine import span
from repro.serving.paged import PagedEngine

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "perfbench"))
import trace_reduce  # noqa: E402

CFG = make_tiny(get("llama-1.5b"))
PS, MAX_NEW, SUFFIX = 8, 3, 5
PREFIX = np.arange(2 * PS, dtype=np.int32) + 3            # two full pages


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    engine = PagedEngine(CFG, init_params(CFG, jax.random.key(0)),
                         page_size=PS, rows=2, max_len=64,
                         prefix_cache=True)
    fleet = FleetController([EngineHandle("e0", engine, CLOUD)])
    cold = RequestSpec(prompt=PREFIX, rid="cold", max_new_tokens=MAX_NEW,
                       tenant="t")
    warm = [RequestSpec(prompt=np.concatenate(
                [PREFIX, np.arange(SUFFIX, dtype=np.int32) + 40 + 10 * i]),
                rid=f"warm{i}", max_new_tokens=MAX_NEW, tenant="t")
            for i in range(2)]
    keys = []                          # attended keys per decode step
    step = engine.step

    def counted_step(**kw):
        keys.append(sum(len(r.prompt) + len(r.output) + 1
                        for r in engine.requests.values() if not r.done))
        return step(**kw)

    engine.step = counted_step
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        for batch in ([cold], warm):
            tickets = [fleet.submit(s) for s in batch]
            while not all(t.done for t in tickets):
                fleet.step()
    tr = trace_reduce.load(trace_reduce.find_xplane(str(out)))
    return {"tr": tr, "engine": engine, "fleet": fleet, "keys": keys}


def _spans(tr, name):
    return [h for h in tr.host if h.name == name]


def _within(inner, outer):
    return (inner.module == outer.module and outer.start <= inner.start
            and inner.end <= outer.end)


def test_every_span_of_the_serving_path_is_recorded(served):
    tr = served["tr"]
    for name in ("fleet.step", "fleet.dispatch", "engine.admit",
                 "prefix.match", "engine.prefill", "engine.suffix_prefill",
                 "prefix.donate", "engine.decode", "engine.sync",
                 "engine.first_run"):
        assert _spans(tr, name), name
    steps = _spans(tr, "fleet.step")
    for name in ("fleet.dispatch", "engine.admit", "engine.decode"):
        assert all(any(_within(h, s) for s in steps)
                   for h in _spans(tr, name)), name


def test_device_waits_nest_inside_decode_and_donation(served):
    tr = served["tr"]
    syncs = _spans(tr, "engine.sync")
    for outer, site in (("engine.decode", "tokens"),
                        ("prefix.donate", "page_table")):
        for h in _spans(tr, outer):
            assert any(_within(s, h) and s.stats["site"] == site
                       for s in syncs), outer
    for h in _spans(tr, "prefix.match"):
        assert not any(_within(s, h) for s in syncs)


def test_span_counts_equal_what_the_engine_did(served):
    tr, engine = served["tr"], served["engine"]
    admits = [(h.stats["prompt"], h.stats["cached"])
              for h in _spans(tr, "engine.admit")]
    plen = len(PREFIX)
    assert admits == [(plen, 0), (plen + SUFFIX, plen),
                      (plen + SUFFIX, plen)]
    assert [h.stats["tokens"]
            for h in _spans(tr, "engine.suffix_prefill")] == [SUFFIX] * 2
    decodes = _spans(tr, "engine.decode")
    assert [h.stats["keys"] for h in decodes] == served["keys"]
    assert [h.stats["rows"] for h in decodes] == [1] * MAX_NEW + [2] * MAX_NEW
    assert engine.last_prefix_hit == plen
    assert all(isinstance(h.stats["clock"], float)
               for h in _spans(tr, "fleet.step"))


def test_each_row_and_length_is_one_first_run(served):
    tr, fleet = served["tr"], served["fleet"]
    runs = [h.stats["key"] for h in _spans(tr, "engine.first_run")]
    assert sorted(runs) == sorted([
        f"prefill[slot=0,plen={len(PREFIX)}]", "decode",
        f"suffix[slot=0,slen={SUFFIX}]", f"suffix[slot=1,slen={SUFFIX}]"])
    assert {h.stats["program"] for h in _spans(tr, "engine.first_run")} \
        == {"prefill", "decode", "suffix"}
    jits = [sp.name for sp in fleet.tracer.spans if sp.kind == "jit"]
    for row in (0, 1):
        assert jits.count(f"jit:suffix[slot={row},slen={SUFFIX}]") == 1
    for h in _spans(tr, "engine.first_run"):     # fenced by its own wait
        assert any(_within(s, h) and s.stats["site"] == "first_run"
                   for s in _spans(tr, "engine.sync"))


def test_programs_are_named_after_their_functions(served):
    engine = served["engine"]
    p, s = engine.params, engine.state
    row = jnp.zeros((1, len(PREFIX)), jnp.int32)
    texts = {
        "decode_step": engine._decode_fn.lower(p, s),
        "prefill": engine._prefill_fn.lower(p, s, row, slot=0,
                                             plen=len(PREFIX)),
        "suffix_prefill": engine._suffix_fn.lower(
            p, s, row[:, :SUFFIX], slot=1, slen=SUFFIX),
    }
    for name, lowered in texts.items():
        assert lowered.as_text().startswith(f"module @jit__paged_{name} ")


def test_counts_are_worked_out_only_while_a_trace_records(tmp_path):
    calls = []

    def count():
        calls.append(1)
        return 7

    with span("probe.off", n=count):
        time.sleep(1e-3)
    assert calls == []
    with jax.profiler.trace(str(tmp_path)):
        with span("probe.on", n=count, site="x"):
            time.sleep(1e-3)
    assert calls == [1]
    tr = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    assert [h.stats for h in _spans(tr, "probe.on")] == [{"n": 7,
                                                          "site": "x"}]
