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
