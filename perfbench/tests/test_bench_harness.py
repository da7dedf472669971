"""The harness finds each cell's files by name and refuses to run off-chip."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import cells  # noqa: E402
import loadgen  # noqa: E402


def test_every_named_file_is_found():
    bench = cells.load_benchmark()
    for c in bench["configs"]:
        conf = cells.config(bench, c["name"])
        assert {"page_size", "rows", "kv_pages"} <= set(conf["serving"])
        assert conf["check"]["served_logit_gap"] > 0
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert callable(cells.metric_reader(metric["name"]))
    for w in bench["workloads"]:
        spec = cells.traffic(w["traffic"])
        cells.config(bench, w["config"])
        e2e = {m["name"] for m in cells.end_to_end(bench, w["name"])}
        assert "setup_s" in e2e and len(e2e) > 1
        assert cells.per_layer(bench, w["name"])
        t = loadgen.Traffic(spec, 2 ** 31 + 5, 1000)
        assert t.initial(10.0)


def test_new_cell_and_metric_are_taken_without_edits(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    bench = cells.load_benchmark()
    (root / "perfbench" / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 2.0, "prompt_tokens": [[8, 1]],
         "output_tokens": [[4, 1]]}))
    (root / "perfbench" / "metrics" / "new.count.py").write_text(
        "def read(ctx):\n    return float(len(ctx.reqs))\n")
    bench["workloads"].append({"name": "new-cell", "config":
                               bench["configs"][0]["name"],
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new.count", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "fleet", "moves": "setup_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(cells, "HERE", root / "perfbench")
    monkeypatch.setattr(cells, "REPO", root)
    b = cells.load_benchmark()
    assert cells.traffic(cells.workload(b, "new-cell")["traffic"])[
        "rate_per_s"] == 2.0
    names = [m["name"] for m in cells.per_layer(b, "new-cell")]
    assert "new.count" in names

    class Ctx:
        reqs = [1, 2, 3]
    assert cells.metric_reader("new.count")(Ctx) == 3.0


TOY_REFERENCE = '''"""Toy architecture: Llama's decoder with its leaves
under other names, and one more norm before the output head."""

import numpy as np

import weights as W
from reference import llama as L

NAMES = {"attn_norm": "pre_attn", "wq": "q", "wk": "k", "wv": "v",
         "wo": "o", "mlp_norm": "pre_mlp", "w_gate": "gate", "w_up": "up",
         "w_down": "down"}
layer_kinds = L.layer_kinds
token_ops = L.token_ops
paged_attention = L.paged_attention


def global_leaves(conf):
    g = L.global_leaves(conf)
    return {"tokens": g["embed"], "out_norm": g["final_norm"],
            "head_norm": g["final_norm"], "head": g["lm_head"]}


def layer_leaves(conf, kind):
    return {NAMES[n]: s for n, s in L.layer_leaves(conf, kind).items()}


def logits_at(conf, seed, seqs, rows, quant=None):
    key = W.seed_key(seed)
    g = W.global_weights(key, global_leaves(conf))
    eps = conf.get("rms_norm_eps", 1e-6)
    out = []
    for seq, r in zip(seqs, rows):
        x = g["tokens"][np.asarray(seq)].astype(L.F32)
        for layer, kind in enumerate(layer_kinds(conf)):
            drawn = W.layer_weights(key, layer_leaves(conf, kind), layer)
            w = {n: drawn[t] for n, t in NAMES.items()}
            x = x + L.attention(L.rmsnorm(x, w["attn_norm"], eps), w, conf,
                                quant)
            x = x + L.dense_mlp(L.rmsnorm(x, w["mlp_norm"], eps), w, conf,
                                kind, quant)
        h = L.rmsnorm(L.rmsnorm(x, g["out_norm"], eps), g["head_norm"], eps)
        out.append(np.asarray(L.mm("td,dv->tv", h[np.asarray(r)], g["head"],
                                   quant)))
    return out
'''
TOY_PROGRAM = '''"""Toy architecture on the program's dense path (its extra
norm has no place there and is left out)."""

from programs import llama as P

NAMES = {"attn_norm": "pre_attn", "wq": "q", "wk": "k", "wv": "v",
         "wo": "o", "mlp_norm": "pre_mlp", "w_gate": "gate", "w_up": "up",
         "w_down": "down"}
model_config = P.model_config


def global_tree(g, cfg):
    return P.global_tree({"embed": g["tokens"], "final_norm": g["out_norm"],
                          "lm_head": g["head"]}, cfg)


def layer_tree(leaves, kind):
    return P.layer_tree({n: leaves[t] for n, t in NAMES.items()}, kind)
'''
TOY_CONFIG = {"model_type": "toyarch", "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 2,
              "num_key_value_heads": 1, "head_dim": 32,
              "num_hidden_layers": 2, "vocab_size": 1024,
              "serving": {"page_size": 16, "rows": 2, "kv_pages": 16},
              "check": {"served_logit_gap": 0.5}}


def _copied_checkout(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    monkeypatch.setattr(cells, "HERE", root / "perfbench")
    monkeypatch.setattr(cells, "REPO", root)
    return root


def test_new_architecture_is_taken_without_edits(tmp_path, monkeypatch):
    bench = cells.load_benchmark()
    root = _copied_checkout(tmp_path, monkeypatch)
    pb = root / "perfbench"
    (pb / "reference" / "toyarch.py").write_text(TOY_REFERENCE)
    (pb / "programs" / "toyarch.py").write_text(TOY_PROGRAM)
    (pb / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    bench["configs"].append({"name": "toy", "source": "x",
                             "file": "perfbench/configs/toy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy",
                               "traffic": "agent-shared", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    import flops
    import sut
    import weights as W
    b = cells.load_benchmark()
    conf = cells.config(b, cells.workload(b, "toy-cell")["config"])
    ref = cells.reference(conf)
    assert Path(ref.__file__) == pb / "reference" / "toyarch.py"
    assert Path(cells.program(conf).__file__) == \
        pb / "programs" / "toyarch.py"
    assert "head_norm" in ref.global_leaves(conf)
    seq = np.arange(20, dtype=np.int32) * 7 % conf["vocab_size"]
    (lg,) = ref.logits_at(conf, 3, [seq], [[0, 19]])
    assert lg.shape == (2, conf["vocab_size"]) and np.isfinite(lg).all()
    assert flops.token_ops(conf, 5, True) > 0
    cfg = sut.model_config("toy", conf)
    params = sut.program_params(conf, cfg, 3)      # checked against the
    np.testing.assert_array_equal(                 # program's schema
        np.asarray(params["blocks"][0][0]["attn"]["wq"][1]),
        np.asarray(W.layer_weights(W.seed_key(3), ref.layer_leaves(
            conf, "dense"), 1)["q"]))


@pytest.mark.parametrize("look", ["reference", "program"])
def test_an_architecture_without_its_module_is_named(tmp_path, monkeypatch,
                                                     look):
    _copied_checkout(tmp_path, monkeypatch)
    conf = dict(TOY_CONFIG, model_type="nosucharch")
    where = {"reference": "perfbench/reference/nosucharch.py",
             "program": "perfbench/programs/nosucharch.py"}[look]
    with pytest.raises(FileNotFoundError, match=where):
        getattr(cells, look)(conf)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "ds7b-agent-shared", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_refuses_a_machine_without_a_tpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


OPEN = {"loop": "open", "rate_per_s": 2.0, "ramp_s": 5.0,
        "prompt_tokens": [[512, 8], [1024, 7], [2048, 5]],
        "output_tokens": [[32, 1], [64, 1]]}


@pytest.mark.parametrize("spec", [cells.traffic("agent-shared"), OPEN],
                         ids=["closed", "open"])
def test_same_seed_same_traffic_and_every_seed_the_same_mix(spec):
    def turns(seed):
        t = loadgen.Traffic(spec, seed, 1000)
        out = t.initial(30.0)
        if t.loop == "closed":            # each session's next two turns
            out += [n for r in list(out) for n in t.after(r, r.due + 9.0)]
            out += [n for r in out[-len(t.streams):]
                    for n in t.after(r, r.due + 9.0)]
        return out
    a, b = turns(11), turns(11)
    assert [(r.rid, r.due) for r in a] == [(r.rid, r.due) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    c = turns(2 ** 31 + 12)
    # another seed: the same sizes and times, other tokens
    assert [(r.rid, r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.rid, r.due, len(r.prompt), r.max_new) for r in c]
    assert not any((x.prompt[-8:] == y.prompt[-8:]).all()
                   for x, y in zip(a, c))
