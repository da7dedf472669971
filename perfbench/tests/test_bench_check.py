"""The comparison that decides ``correct``, driven through a whole run.

Each test runs the harness at a tiny size on the CPU (no look for a
chip), over the normal serving path, and reads ``correct``:

* a sound run is correct;
* each fault a served cell can have, planted in the timed path, makes it
  not correct: a decode step that returns its state unchanged, and a
  token altered where the engine produces it;
* the control -- the reference computed in fp8, the precision below the
  bf16 the configuration states, put in the program's place for the
  check -- makes it not correct.

The program re-feeds a prompt's last token at position ``len(prompt)``
on its first decode step (PERF.md, Open questions), so its served
streams are not the model's continuation of the prompt.  The sound run
here needs a program without that fault: where a probe shows it, the
test moves each admitted row's position back by one, which makes the
first decode step recompute the last prompt token at its own position.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import cells  # noqa: E402
import harness  # noqa: E402
import sut  # noqa: E402
from reference import check  # noqa: E402

# norm_topk_prob true: the only routing the program implements today
TINY = {
    "model_type": "deepseek", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
    "num_hidden_layers": 3, "vocab_size": 1024, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 64, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "serving": {"page_size": 16, "rows": 4, "kv_pages": 64},
    "check": {"served_logit_gap": 0.15},   # sound <= 0.036, fp8 >= 0.53
}
# the same limit holds the Llama decoder: sound 0.012, fp8 0.33 (seed 7)
TINY_DENSE = {k: v for k, v in TINY.items() if k not in (
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "first_k_dense_replace", "norm_topk_prob")}
TINY_DENSE["model_type"] = "llama"
ARCHS = [pytest.param(TINY_DENSE, id="llama"),
         pytest.param(TINY, id="deepseek")]
TRAFFIC = {
    "loop": "closed", "sessions": 4, "think_s": 0.05, "ramp_s": 0.5,
    "tenants": 2,
    "tenant_prefix_tokens": 32, "prompt_tokens": [[16, 1]],
    "output_tokens": [[8, 1]],
}
LIMIT = TINY["check"]["served_logit_gap"]
SEED = 7


@functools.cache
def first_decode_refeeds() -> bool:
    """Does the engine's first decode step feed the last prompt token
    again at position len(prompt)?  Judged by which context the float32
    reference finds the first served token closer to."""
    from repro.serving.engine import Request
    cfg = sut.model_config("probe", TINY)
    params = sut.program_params(TINY, cfg, 1)
    eng = sut.PagedEngine(cfg, params, page_size=16, pages=8, rows=1,
                          max_len=64)
    prompt = (np.arange(37, dtype=np.int32) * 31) % TINY["vocab_size"]
    eng.add_request(Request("p", prompt, max_new_tokens=1))
    tok = eng.step()["p"]
    twice = np.concatenate([prompt, prompt[-1:]])
    plain, dup = cells.reference(TINY).logits_at(
        TINY, 1, [prompt, twice], [[len(prompt) - 1], [len(twice) - 1]])
    return check.gaps(dup, [tok])[0] < check.gaps(plain, [tok])[0]


def fix_first_decode(engine):
    """Stand-in for a program without the re-fed token (see above)."""
    if not first_decode_refeeds():
        return
    add = engine.add_request

    def add_request(req, **kw):
        ok = add(req, **kw)
        if ok:
            s = engine.state
            engine.state = dataclasses.replace(
                s, positions=s.positions.at[req.slot].add(-1))
        return ok
    engine.add_request = add_request


def state_unchanged(engine):
    """Fault: every decode step returns the state it was given."""
    fix_first_decode(engine)
    real = engine._decode_fn
    engine._decode_fn = lambda params, state: (state, real(params, state)[1])


def token_altered(engine):
    """Fault: each request's second token is changed as it is produced."""
    fix_first_decode(engine)
    step = engine.step

    def altered(**kw):
        out = step(**kw)
        for req in engine.requests.values():
            if len(req.output) == 2:
                req.output[-1] = (req.output[-1] + 1) % TINY["vocab_size"]
        return out
    engine.step = altered


def _run(tamper, conf, keep=None, control=False):
    return harness.run(
        config_name="tiny-" + conf["model_type"], conf=conf,
        traffic_spec=TRAFFIC, seed=SEED, seconds=3.0, trace=False,
        metrics=[], t_start=time.perf_counter(),
        devices=jax.devices(), tamper=tamper, control=control, keep=keep)


@pytest.fixture(scope="module", params=ARCHS)
def sound(request):
    keep: dict = {}
    return _run(fix_first_decode, request.param, keep), keep


def test_sound_run_is_correct(sound):
    res, keep = sound
    assert res["info"]["window_compiles"] == 0
    assert res["checks"]["served_logit_gap"]["value"] <= LIMIT
    assert res["correct"] is True
    assert res["attempted"] > len(keep["sample"]) > 0


@pytest.mark.parametrize("conf", ARCHS)
@pytest.mark.parametrize("fault", [state_unchanged, token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_fault_makes_run_incorrect(fault, conf):
    res = _run(fault, conf)
    assert res["correct"] is False
    assert res["checks"]["served_logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("conf", ARCHS)
def test_control_reads_above_the_limit(conf):
    res = _run(fix_first_decode, conf, control=True)
    assert res["correct"] is False
    assert res["checks"]["served_logit_gap"]["value"] > LIMIT
