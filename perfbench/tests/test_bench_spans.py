"""The serving path's own spans, read from a small trace recorded on one
TPU v5e.

``data/serving_spans.xplane.pb`` (``record_spans_fixture.py``): a
2-layer engine (2 KV heads of 128, a 4-page page table, 2 rows) admits
a 24-token prompt whose first 16 tokens its prefix cache holds -- a
suffix prefill of 8 tokens -- and decodes 3 steps, inside one
``bench.window`` span with a ``bench.step`` span per fleet step.  The
metrics that read those spans are given a stand-in for the harness's
context; on the older fixture, recorded from a program without the
spans, each finds nothing.

The decode and prefill programs and the paged decode-attention kernel
are found by name (``jit__paged_decode_step``, ...,
``paged_decode_attention``); on the newer fixture that finds what the
older lookup by the kernel's first operand found.  The older fixture's
programs and kernels have no names (``jit__unknown``, ``%closed_call``),
so there the readers find nothing.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import pytest  # noqa: E402

import cells  # noqa: E402
import flops  # noqa: E402
import spans as S  # noqa: E402
import trace_reduce as T  # noqa: E402

DATA = HERE / "tests" / "data"
NP, SUFFIX, STEPS = 4, 8, 3
READERS = ("step.suffix_ms_per_token", "engine.admit_wait_ms",
           "fleet.host_ms_per_step")
NAMED = ("step.decode_ms", "step.prefill_ms_per_req",
         "paged_decode_attention_roofline")
# the fixtures' engine (record_spans_fixture.py): 16-token pages, a
# 64-token budget; one row's 24-token prompt, 16 of it cached, 3 steps
FIXTURE_CONF = {"model_type": "llama", "hidden_size": 256,
                "intermediate_size": 512, "num_attention_heads": 2,
                "num_key_value_heads": 2, "head_dim": 128,
                "num_hidden_layers": 2, "vocab_size": 1024}


@dataclass
class Ctx:
    trace: object
    trace_span: tuple
    conf: dict = None
    max_len: int = 64
    serving: dict = None
    decodes: tuple = ((0, [25]), (0, [26]), (0, [27]))
    admissions: tuple = ((0, 16 + SUFFIX, 16),)

    def __post_init__(self):
        self.conf = self.conf or FIXTURE_CONF
        self.serving = self.serving or {"page_size": 16}

    def peak(self):
        return flops.peak("TPU v5 lite")


@pytest.fixture(scope="module")
def ctx():
    tr = T.load(str(DATA / "serving_spans.xplane.pb"))
    return Ctx(tr, tr.span("bench.window"))


def _one(ctx, name):
    (h,) = S.starting_in(ctx.trace, name, *ctx.trace_span)
    return h


def test_suffix_program_is_found_by_name_and_by_its_kernel(ctx):
    paged = T.pallas_call("s32[")
    by_kernel = T.modules_with(ctx.trace, lambda op: paged(op)
                               and T.operand_size(op) == NP)
    (module,) = by_kernel
    assert module.startswith("jit__paged_suffix_prefill(")
    assert _one(ctx, "engine.suffix_prefill").stats["tokens"] == SUFFIX
    ns = T.time_where(ctx.trace, lambda op: op.module == module,
                      *ctx.trace_span)
    got = cells.metric_reader("step.suffix_ms_per_token")(ctx)
    assert got == pytest.approx(ns / 1e6 / SUFFIX)
    assert got > 0


def _ev(name, where, start, dur, **stats):
    return T.Op(name, where, start, dur, stats)


def test_a_scan_is_counted_whole_or_not_at_all():
    """A window that ends mid-scan counts the whole scan of a suffix span
    that starts in it, from the host's wait before the span (the device
    clock may place the scan's first ops before it) to the end of the
    next; a window that starts after the span counts none, nor does a
    trace that ends before the next wait."""
    host = [_ev("engine.sync", "python3", 0, 10, site="tokens"),
            _ev("engine.suffix_prefill", "python3", 20, 5, tokens=4),
            _ev("engine.sync", "python3", 30, 100, site="page_table")]
    scan = [_ev("%loop", "jit__paged_suffix_prefill(1)", 15 + 10 * i, 10)
            for i in range(10)]
    decode = _ev("%fusion", "jit__paged_decode_step(2)", 120, 5)
    tr = T.Trace(devices={"/device:TPU:0": scan + [decode]}, host=host)
    read = cells.metric_reader("step.suffix_ms_per_token")
    whole = 100 / 1e6 / 4
    assert read(Ctx(tr, (0, 200))) == pytest.approx(whole)
    assert read(Ctx(tr, (0, 60))) == pytest.approx(whole)
    assert read(Ctx(tr, (21, 200))) is None
    tr.host = host[:2]
    assert read(Ctx(tr, (0, 200))) is None


def test_admission_wait_is_its_nested_device_waits(ctx):
    admit = _one(ctx, "engine.admit")
    assert (admit.stats["prompt"], admit.stats["cached"]) == \
        (16 + SUFFIX, 16)
    waits = S.inside(S.waits(ctx.trace), admit)
    donate = _one(ctx, "prefix.donate")
    assert [w.stats["site"] for w in S.inside(waits, donate)] == \
        ["page_table"]
    got = cells.metric_reader("engine.admit_wait_ms")(ctx)
    assert got == pytest.approx(S.wait_ns(waits, admit) / 1e6)
    assert 0 < got < admit.dur / 1e6


def test_host_time_per_step_leaves_out_the_waits(ctx):
    steps = S.starting_in(ctx.trace, "fleet.step", *ctx.trace_span)
    assert len(steps) == STEPS
    decodes = S.starting_in(ctx.trace, "engine.decode", *ctx.trace_span)
    assert [h.stats["rows"] for h in decodes] == [1] * STEPS
    syncs = S.waits(ctx.trace)
    assert all(S.inside(syncs, h) for h in decodes)
    got = cells.metric_reader("fleet.host_ms_per_step")(ctx)
    mean = sum(h.dur for h in steps) / STEPS / 1e6
    assert 0 < got < mean
    assert got == pytest.approx(mean - sum(
        S.wait_ns(syncs, h) for h in steps) / STEPS / 1e6)


def test_fleet_clock_maps_onto_the_trace(ctx):
    """Each step's fleet-clock reading less its start on the profiler's
    timeline is one offset: fleet times can be placed on the trace."""
    steps = S.starting_in(ctx.trace, "fleet.step", *ctx.trace_span)
    offsets = [h.stats["clock"] - h.start / 1e9 for h in steps]
    assert max(offsets) - min(offsets) < 1e-3


@pytest.mark.parametrize("name", READERS)
def test_a_trace_without_the_spans_reads_nothing(name):
    tr = T.load(str(DATA / "paged_decode.xplane.pb"))
    assert cells.metric_reader(name)(Ctx(tr, tr.span("bench.window"))) \
        is None


def by_operand(name):
    """The reader as it found its programs and kernel before they had
    names: by the Pallas call's first operand (the page table ``s32[``,
    one row's or wider; activations ``bf16[`` for the cold prefill)."""
    read = cells.metric_reader(name)
    g = read.__globals__
    paged, flash = T.pallas_call("s32["), T.pallas_call("bf16[")

    def lookup(ctx):
        np_pages = ctx.max_len // ctx.serving["page_size"]
        if name == "paged_decode_attention_roofline":
            g["KERNEL"] = paged
            return read(ctx)
        if name == "step.decode_ms":
            mods = T.modules_with(ctx.trace, lambda op: paged(op)
                                  and T.operand_size(op) > np_pages)
            key = "PROGRAM"
        else:
            mods = T.modules_with(ctx.trace, lambda op: flash(op) or (
                paged(op) and T.operand_size(op) == np_pages))
            key = "PROGRAMS"
        g[key] = lambda op: op.module in mods
        return read(ctx)
    return lookup


@pytest.mark.parametrize("name", NAMED)
def test_programs_and_kernel_found_by_name_as_by_operand(ctx, name):
    c = Ctx(ctx.trace, ctx.trace_span)
    got = cells.metric_reader(name)(c)
    assert got is not None and got > 0
    assert got == by_operand(name)(c)


@pytest.mark.parametrize("name", NAMED)
def test_a_trace_without_program_names_reads_nothing(name):
    tr = T.load(str(DATA / "paged_decode.xplane.pb"))
    c = Ctx(tr, tr.span("bench.window"))
    assert by_operand(name)(c) > 0
    assert cells.metric_reader(name)(c) is None
