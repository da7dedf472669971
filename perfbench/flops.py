"""Operations and bytes the algorithms need, computed from shapes.

Kernel counts are of what the computation requires, not of what a given
implementation happens to move: a kernel that reads more (dead pages,
padding) shows it as a lower share of its roofline.  bf16 operands are 2
bytes.  A multiply-add is two operations.

Model counts are the operations a token requires: its matrix products
(for an MoE layer the routed top-k experts, the shared experts and the
router only, whatever the program runs), attention over the keys it
attends to, and the output head where its logits are needed.  Each
architecture counts its own (``token_ops`` of its reference module,
found by the configuration's ``model_type``).
"""

from __future__ import annotations

import json
from pathlib import Path

import cells

BF16 = 2


# -- kernels -----------------------------------------------------------------

def flash_attention(S: int, H: int, KV: int, D: int, B: int = 1,
                    causal: bool = True) -> tuple[float, float]:
    """(ops, bytes) of one attention call over B sequences of S tokens."""
    pairs = S * (S + 1) / 2 if causal else S * S
    ops = 4.0 * B * H * D * pairs                # QK^T and PV
    byts = float(B * S * D * (2 * H + 2 * KV) * BF16)   # q, k, v in; o out
    return ops, byts


def paged_decode_attention(ctx_lens, H: int, KV: int,
                           D: int) -> tuple[float, float]:
    """(ops, bytes) of one decode-attention call: one query token per
    row, row i attending to ``ctx_lens[i]`` cached tokens."""
    keys = float(sum(ctx_lens))
    ops = 4.0 * H * D * keys
    byts = (2 * KV * D * keys + 2 * H * D * len(ctx_lens)) * BF16
    return ops, byts


# -- model -------------------------------------------------------------------

def token_ops(conf: dict, ctx: int, head: bool) -> float:
    """Operations one token needs at a position attending ``ctx`` keys."""
    return cells.reference(conf).token_ops(conf, ctx, head)


def prompt_ops(conf: dict, start: int, n: int) -> float:
    """Tokens start..start+n-1 of a prompt, no logits needed."""
    count = cells.reference(conf).token_ops
    return sum(count(conf, p + 1, False) for p in range(start, start + n))


def peak(device_kind: str) -> dict:
    """{"flops": ..., "bytes_per_s": ...} of one chip, from peaks.json;
    an unknown device is an error, never a default."""
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        peaks = json.load(f)
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; add them with their source")
    return peaks["devices"][device_kind]
