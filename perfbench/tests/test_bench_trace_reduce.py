"""Trace reduction on a small trace recorded on one TPU v5e.

``data/paged_decode.xplane.pb``: a 2-layer engine (2 KV heads of 128,
4-page page table, 2 rows) admits a 24-token prompt whose first 16
tokens its prefix cache holds -- a suffix prefill of 8 tokens, one
paged-attention call per token and layer on a one-row page table --
then takes 3 decode steps (one call per layer on the two-row table),
all inside one ``bench.window`` span with a ``bench.step`` span per
step.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import pytest  # noqa: E402

import trace_reduce as T  # noqa: E402

FIXTURE = HERE / "tests" / "data" / "paged_decode.xplane.pb"
LAYERS, NP, ROWS = 2, 4, 2


@pytest.fixture(scope="module")
def tr():
    return T.load(str(FIXTURE))


def test_planes_and_host_spans(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    assert tr.span("bench.window") is not None
    assert sum(1 for h in tr.host if h.name == "bench.step") == 3


def test_kernels_and_programs_are_told_apart(tr):
    paged = T.pallas_call("s32[")
    calls = [T.operand_size(op) for op in tr.devices["/device:TPU:0"]
             if paged(op)]
    assert calls.count(NP) == 8 * LAYERS            # suffix: one row
    assert calls.count(ROWS * NP) == 3 * LAYERS     # decode: both rows
    suffix = T.modules_with(tr, lambda op: paged(op)
                            and T.operand_size(op) == NP)
    decode = T.modules_with(tr, lambda op: paged(op)
                            and T.operand_size(op) > NP)
    assert len(suffix) == len(decode) == 1 and suffix != decode
    assert not T.modules_with(tr, T.pallas_call("bf16["))   # no cold prefill


def test_busy_time_counts_nested_ops_once(tr):
    t0, t1 = tr.span("bench.window")
    busy = T.busy_ns(tr, t0, t1)
    assert 0 < busy <= t1 - t0
    assert T.time_where(tr, lambda op: True, t0, t1) == busy
    paged = T.pallas_call("s32[")
    decode = T.modules_with(tr, lambda op: paged(op)
                            and T.operand_size(op) > NP)
    kernel = T.time_where(tr, lambda op: paged(op) and op.module in decode,
                          t0, t1)
    program = T.time_where(tr, lambda op: op.module in decode, t0, t1)
    assert 0 < kernel < program <= busy


def test_breakdown_lists(tr):
    t0, t1 = tr.span("bench.window")
    top = T.top_ops(tr, t0, t1)
    assert 0 < len(top) <= 10
    assert all(s > 0 and " while(" not in name for name, s in top)
    assert any("tpu_custom_call" in name for name, _ in top)
    gaps = T.idle_gaps(tr, t0, t1)
    assert 0 < len(gaps) <= 10
    assert sum(s for _, s in gaps) <= (t1 - t0) / 1e9 - T.busy_ns(
        tr, t0, t1) / 1e9 + 1e-9


def test_operand_parsing():
    op = T.Op("%closed_call.3 = bf16[1,32,1,128] custom-call(s32[2304]{0} "
              "%a, bf16[8,2]{0} %b), custom_call_target=\"tpu_custom_call\"",
              "m", 0, 1, {})
    assert T.pallas_call("s32[")(op) and not T.pallas_call("bf16[")(op)
    assert T.operand_size(op) == 2304
    assert T.short_name(op) == "%closed_call.3 tpu_custom_call"


def test_programs_and_kernels_by_name():
    op = T.Op("%paged_decode_attention.9 = bf16[2,2,1,128] custom-call("
              "s32[8]{0} %a), custom_call_target=\"tpu_custom_call\"",
              "jit__paged_decode_step(13756119872087562979)", 0, 1, {})
    assert T.kernel("paged_decode_attention")(op)
    assert not T.kernel("decode_attention")(op)
    assert T.in_program("jit__paged_decode_step")(op)
    assert T.in_program("jit__paged_prefill", "jit__paged_decode_step")(op)
    assert not T.in_program("jit__paged_prefill")(op)
    fusion = T.Op("%paged_decode_attention.9 = bf16[2] fusion(%a)",
                  "jit__paged_decode_step(1)", 0, 1, {})
    assert not T.kernel("paged_decode_attention")(fusion)
