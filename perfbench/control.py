"""Readings that set a cell's limit on the served logit gap (chip only).

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 51

For each seed, in one process: one run of the cell, as the benchmark
makes it, then on the requests its check compared, through the same
check (``harness.judge``):

  served   the run's own reading: the widest reference gap of the served
           tokens, and whether the run is correct;
  program  the widest reference gap of the token the program's own full
           forward pass puts first at the same positions (the prefill
           kernels, no cache; its bf16 arithmetic against float32);
  control  the same for the reference itself computed in fp8 (the
           nearest precision below the bf16 the configuration states):
           it must come out as not correct.

One JSON line per seed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as entry  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    jax = entry.setup_jax()
    import cells
    import harness
    import sut
    from reference import check

    bench = cells.load_benchmark()
    cell = cells.workload(bench, args.workload)
    conf = cells.config(bench, cell["config"])
    ref = cells.reference(conf)
    devices = entry.require_chip(jax, cell["chips"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        keep: dict = {}
        res = harness.run(
            config_name=cell["config"], conf=conf,
            traffic_spec=cells.traffic(cell["traffic"]), seed=seed,
            seconds=args.seconds, trace=False,
            metrics=cells.end_to_end(bench, cell["name"]), t_start=t0,
            devices=devices, keep=keep)
        sample, exact = keep["sample"], keep["exact"]
        seqs, rows = map(list, zip(*(check.rows_of(r) for r in sample)))
        params = sut.program_params(conf, keep["cfg"], seed)
        prog = sut.teacher_forced_argmax(keep["cfg"], params, seqs, rows,
                                         keep["max_len"])
        del params
        gc.collect()
        low = [lg.argmax(-1) for lg in
               ref.logits_at(conf, seed, seqs, rows, quant="fp8")]
        readings = {k: harness.judge(conf, sample, exact, chosen)
                    for k, chosen in (("program", prog), ("control", low))}
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "served": res["checks"]["served_logit_gap"]["value"],
            **{k: v["checks"]["served_logit_gap"]["value"]
               for k, v in readings.items()},
            **{f"{k}_correct": v["correct"] for k, v in readings.items()},
            "sampled": len(sample),
            "sampled_tokens": sum(len(r) for r in rows),
            "finished": res["info"]["finished"],
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "setup_phases_s": res["info"]["setup_phases_s"],
            "check_s": res["info"]["check_s"],
            "window_compiles": res["info"]["window_compiles"],
            "seconds": time.perf_counter() - t0}), flush=True)
        del exact, low, keep
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
