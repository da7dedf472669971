"""Run one cell of the benchmark once, on the chip it is started on.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
BENCHMARK.json at the root of the checkout.  The run refuses anything but
a TPU with as many chips as the cell asks for, and any kernel backend but
``pallas``: it then exits 1 and prints no result.  Otherwise the last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``), ``info``, and last ``checks``, each number compared with
its limit; the same comparison ends standard error.

JAX's persistent compilation cache lives at perfbench/.jax_cache in the
checkout, whatever the environment says, so only a cell's first run in a
checkout compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CACHE = HERE / ".jax_cache"


def fail(msg: str):
    print(f"perfbench: {msg}", file=sys.stderr)
    raise SystemExit(1)


def setup_jax():
    """JAX with the persistent compilation cache in the checkout (and the
    TPU runtime's own log files off: they would go to a fixed /tmp path)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def require_chip(jax, chips: int):
    """The cell's TPU devices, the chip's peaks and the Pallas kernels,
    or exit 1 before any result is printed."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no accelerator: {e}")
    d = devices[0]
    if d.platform != "tpu":
        fail(f"JAX found platform {d.platform!r} ({d.device_kind}), not a "
             f"TPU; the benchmark never falls back")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devices)}")
    import flops
    try:
        flops.peak(d.device_kind)
    except KeyError as e:
        fail(str(e))
    try:
        import sut
    except ImportError as e:
        fail(f"the program under test is missing: {e}")
    if sut.ops.backend() != "pallas":
        fail(f"kernel backend is {sut.ops.backend()!r}, not 'pallas'")
    return devices[:chips]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a non-negative integer")
    jax = setup_jax()
    import cells
    try:
        bench = cells.load_benchmark()
        cell = cells.workload(bench, args.workload)
        conf = cells.config(bench, cell["config"])
        traffic = cells.traffic(cell["traffic"])
    except (OSError, KeyError, ValueError) as e:
        fail(f"cannot load the cell: {e}")
    devices = require_chip(jax, cell["chips"])
    import harness

    metrics = (cells.per_layer(bench, cell["name"]) if args.trace
               else cells.end_to_end(bench, cell["name"]))
    result = harness.run(
        config_name=cell["config"], conf=conf,
        traffic_spec=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), metrics=metrics,
        t_start=T_START,
        devices=devices)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
