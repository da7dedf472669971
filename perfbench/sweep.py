"""Find the load a cell's system sustains (chip only).

    python3 perfbench/sweep.py --workload ds7b-agent-shared \
        --key sessions --values 1,2,3,4,5 --seeds 5,6 --seconds 30

One process builds and warms the system once (for the largest value),
then for each seed (choosing the tokens of every turn; the tenant
prompts stay those cached at set-up) and each value of one traffic
parameter (the closed loop's ``sessions``, the open loop's
``rate_per_s``), swapped into the cell's traffic file, serves the
traffic's ramp and a window of
``--seconds``, lets what is in flight finish, and prints one JSON line:
requests due and finished in the window, the backlog unserved at the
close, output tokens per second, and time to first token and the gap
between tokens at their tails.  A cell's load is set once, from this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run as entry


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--key", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=NUMBER",
                    help="another traffic parameter to override")
    args = ap.parse_args(argv)
    jax = entry.setup_jax()
    import cells
    import harness
    import loadgen

    bench = cells.load_benchmark()
    cell = cells.workload(bench, args.workload)
    conf = cells.config(bench, cell["config"])
    devices = entry.require_chip(jax, cell["chips"])
    values = [float(v) for v in args.values.split(",")]
    values = [int(v) if v.is_integer() else v for v in values]
    base = cells.traffic(cell["traffic"])
    for kv in args.set:
        k, v = kv.split("=")
        base[k] = float(v)
    seeds = [int(s) for s in args.seeds.split(",")]
    clock = time.perf_counter
    t = clock()
    sut = harness.Bench(config_name=cell["config"], conf=conf,
                        traffic_spec=dict(base, **{args.key: max(values)}),
                        seed=seeds[0], clock=clock, annotate=False)
    print(json.dumps({"setup_s": clock() - t}), flush=True)
    readers = {n: cells.metric_reader(n)
               for n in ("output_tok_s", "ttft_p95_ms", "fleet.itl_p99_ms")}
    for seed in seeds:
        for value in values:
            spec = dict(base, **{args.key: value})
            traffic = loadgen.Traffic(spec, seed, conf["vocab_size"],
                                      tag=f"{seed}.{value}.")
            traffic.prefix = sut.prefix   # as cached at set-up, every seed
            t0, t1, reqs = sut.serve(traffic, args.seconds, False)
            ctx = harness.Ctx(
                conf=conf, seconds=args.seconds, t0=t0, t1=t1, setup_s=0.0,
                setup_programs=0, reqs=reqs, admissions=[], decodes=[],
                device_kind=devices[0].device_kind, max_len=sut.max_len,
                serving=sut.serving)
            due = [r for r in reqs if ctx.inside(r.t_due)]
            line = {
                "seed": seed, args.key: value, "due": len(due),
                "finished": sum(1 for r in reqs if r.t_done is not None
                                and ctx.inside(r.t_done)),
                "unserved_at_close": sum(1 for r in due if not r.t_tokens),
                **{n: f(ctx) for n, f in readers.items()},
            }
            for _ in range(100_000):          # let what is in flight finish
                if all(r.ticket.done for r in sut.live.values()):
                    break
                sut.fleet.step()
            print(json.dumps(line), flush=True)
    print(json.dumps({"device": harness.device_info(devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
