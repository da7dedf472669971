"""Serving driver: batched requests through the full MVVM stack --
engine + privacy daemon + validation + (optional) speculation.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch llama-1.5b --tiny --requests 8 --max-new 24 --validate
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-1.5b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--validate", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get
    from repro.configs.tiny import make_tiny
    from repro.core.daemon import PrivacyAwareDaemon
    from repro.core.validation import ValidationFramework
    from repro.models.init import init_params
    from repro.serving.engine import Engine, Request

    cfg = get(args.arch)
    if args.tiny:
        cfg = make_tiny(cfg)
    params = init_params(cfg, jax.random.key(args.seed))
    engine = Engine(cfg, params, slots=args.slots, max_len=args.max_len,
                    seed=args.seed)
    daemon = PrivacyAwareDaemon()
    vf = ValidationFramework() if args.validate else None

    rng = np.random.default_rng(args.seed)
    sensitivities = ["public", "personal", "confidential"]
    reqs = [Request(rid=f"r{i}",
                    prompt=rng.integers(50, cfg.vocab_size, 8),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature, top_k=16,
                    sensitivity=sensitivities[i % 3])
            for i in range(args.requests)]

    t0 = time.time()
    for r in reqs:
        d = daemon.decide(sensitivity=r.sensitivity, cfg=cfg,
                          prefill_tokens=len(r.prompt),
                          decode_tokens=r.max_new_tokens,
                          workspace_bytes=10 ** 7)
        print(f"{r.rid}[{r.sensitivity}] -> {d.target} ({d.reason})")
    # drive the engine directly: admit while slots free, then batch-step
    # (Engine.run() is deprecated in favor of exactly this loop)
    pending = list(reqs)
    outs: dict[str, list[int]] = {}
    while pending or engine.requests:
        while pending and engine.add_request(pending[0]):
            outs[pending[0].rid] = pending[0].output
            pending.pop(0)
        if engine.requests:
            engine.step()
    dt = time.time() - t0
    total_toks = sum(len(v) for v in outs.values())
    for rid, toks in sorted(outs.items()):
        line = f"{rid}: {toks}"
        if vf is not None:
            rep = vf.validate_post_hoc(toks)
            if rep.intervened:
                line += f"  [BLOCKED @{rep.halt_position}: " + ",".join(
                    v.kind for v in rep.verdicts if not v.ok) + "]"
        print(line)
    print(f"{total_toks} tokens in {dt:.2f}s "
          f"({total_toks/dt:.1f} tok/s on {jax.default_backend()})")


if __name__ == "__main__":
    main()
