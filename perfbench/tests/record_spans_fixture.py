"""Record the trace fixture ``data/serving_spans.xplane.pb`` on one chip.

    python3 perfbench/tests/record_spans_fixture.py OUT_DIR

A 2-layer engine (2 KV heads of 128, a 4-page page table, 2 rows) with
its prefix cache armed serves behind the fleet.  Untraced set-up: a
16-token tenant prompt is admitted cold and cached, and one extension of
it by 8 tokens makes the suffix program for row 0 ready.  Traced, inside
one ``bench.window`` span: another 8-token extension is admitted (a
suffix prefill over the cached page) and decodes 3 steps, one
``bench.step`` span per fleet step.  The ``.xplane.pb`` lands under
OUT_DIR; the last line printed is its path.
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import sut  # noqa: E402
import trace_reduce  # noqa: E402

CONF = {
    "model_type": "llama", "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 128,
    "num_hidden_layers": 2, "vocab_size": 1024, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6,
    "serving": {"page_size": 16, "rows": 2, "kv_pages": 16},
}
MAX_LEN, PREFIX, SUFFIX, STEPS = 64, 16, 8, 3


def serve(fleet, prompt, rid, step=lambda f: f.step()):
    ticket = fleet.submit(sut.RequestSpec(prompt=prompt, rid=rid,
                                          max_new_tokens=STEPS,
                                          tenant="t"))
    while not ticket.done:
        step(fleet)


def main(out_dir: str) -> str:
    cfg = sut.model_config("fixture", CONF)
    params = sut.program_params(CONF, cfg, 1)
    fleet, engine = sut.serve(cfg, params, CONF["serving"], MAX_LEN)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, CONF["vocab_size"], PREFIX, dtype=np.int32)

    def extended():
        return np.concatenate([prefix, rng.integers(
            0, CONF["vocab_size"], SUFFIX, dtype=np.int32)])

    serve(fleet, prefix, "cold")
    serve(fleet, extended(), "warm")
    jax.block_until_ready(engine.state)

    def step(f):
        with jax.profiler.TraceAnnotation("bench.step"):
            f.step()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        serve(fleet, extended(), "traced", step)
        jax.block_until_ready(engine.state)
    jax.profiler.stop_trace()
    return trace_reduce.find_xplane(out_dir)


if __name__ == "__main__":
    print(main(sys.argv[1]))
