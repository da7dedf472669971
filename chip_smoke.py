"""Chip smoke: the serving main path on a TPU at llama-1.5b's full width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: cross-mesh migration

One chip: the fleet CLI's own code path (``repro.launch.fleet.main``)
serves a few requests -- ``RequestSpec`` -> router -> two attested
``PagedEngine``s with the prefix cache -> migration wire -- with
random bf16 weights from a fixed seed.  One prompt is 600 tokens long
(over the 512-token attention block and not a multiple of it), two
requests share a tenant prefix (suffix prefill), and one engine is
drained mid-stream, so live slots cross ``extract_slot`` ->
``pack_slot`` -> ``inject_slot``.  Then one prompt's prefill and a few
paged decode steps run twice on the same chip, through the Pallas
kernels and through the jnp oracle, and their logits are compared.

Four chips: a dense ``Engine`` on a (1, 4) ("data", "model") mesh serves
a request, its state is serialized and restored onto (4, 1) mesh
engines; every restored leaf must equal the donor's, and two restores
must decode identical continuations.

The script refuses to run anywhere but on a TPU with the Pallas kernel
backend, and never falls back to the CPU.  Every check raises; the last
line of standard output is one JSON object naming the device, printed
only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "llama-1.5b"
SEED = 0
PAGE = 16
LONG_PROMPT = 600
ORACLE_STEPS = 8
# Largest |logit| gap allowed between the Pallas kernels and the jnp
# oracle over one prompt's prefill + paged decode steps.  Both run the
# model in bf16 and differ only in attention: the kernels round softmax
# probabilities to bf16 for the PV matmul and reduce in another order.
# That rounding compounds through the residual stream: at llama-1.5b's
# width with random weights, the interpret-mode kernels on a CPU differ
# from the oracle by 0.06 at 2 layers, 0.11 at 4, 0.21 at 8 and 0.32 at
# 16, on logits up to ~4.5 in size.  24 layers extrapolate to ~0.5; the
# tolerance doubles that.  A kernel fault (wrong page, mask or head
# mapping) changes the attention outright and moves logits by their
# own scale.
LOGIT_TOL = 1.0


def check(ok: bool, msg: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(chips: int):
    """The device and the kernel backend, before any model work."""
    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"JAX found platform {d.platform!r} ({d.device_kind}), not a "
          f"TPU; this smoke never falls back")
    check(len(devs) >= chips,
          f"needs {chips} TPU devices, JAX found {len(devs)}")
    from repro.kernels import ops
    check(ops.backend() == "pallas",
          f"kernel backend is {ops.backend()!r}, not 'pallas'")
    print(f"jax {jax.__version__}, device_kind {d.device_kind}, "
          f"{len(devs)} device(s), backend {ops.backend()}")
    return devs


def report(devs):
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))


# ---------------------------------------------------------------------------
# one chip: the fleet CLI path
# ---------------------------------------------------------------------------

FLEET_ARGV = [
    "--arch", ARCH, "--seed", str(SEED),
    "--engines", "a:edge,b:edge", "--prefix-cache",
    "--slots", "4", "--max-len", "1024", "--page-size", str(PAGE),
    "--requests", "8", "--max-new", "16", "--tenants", "2",
    "--prompt-lens", f"{LONG_PROMPT},40,40,40,40,40,40,40",
    # one arrival per step, so when engine a drains its peer has free
    # rows to take the live slots
    "--queue-limit", "1", "--drain", "a@4",
]


def serve(argv):
    """Serve through ``repro.launch.fleet.main`` and check the result;
    returns the FleetController."""
    from repro.fleet import RequestState
    from repro.launch import fleet as fleet_cli
    t0 = time.perf_counter()
    fleet = fleet_cli.main(argv)
    wall = time.perf_counter() - t0
    tickets = fleet.tickets
    check(len(tickets) == 8, f"{len(tickets)} tickets, expected 8")
    for rid, t in sorted(tickets.items()):
        check(t.state is RequestState.DONE,
              f"{rid} ended {t.state.value}, not done")
        check(len(t.output) == t.spec.max_new_tokens,
              f"{rid}: {len(t.output)} of {t.spec.max_new_tokens} tokens")
    longest = max(len(t.spec.prompt) for t in tickets.values())
    check(longest == LONG_PROMPT,
          f"longest prompt {longest}, expected {LONG_PROMPT}")
    live = [m for m in fleet.telemetry.migrations if not m.lossy]
    check(live, "no live migration was recorded")
    jits = [s for s in fleet.tracer.spans if s.kind == "jit"]
    for s in jits:
        print(f"compile {s.name[4:]} on {s.engine}: "
              f"{s.attrs['wall_s']:.3f} s"
              f"{' (program cache hit)' if s.attrs.get('cache_hit') else ''}")
    check(any(s.name.startswith("jit:suffix[") for s in jits),
          "no suffix prefill ran (no shared tenant prefix was hit)")
    toks = sum(len(t.output) for t in tickets.values())
    print(f"served {len(tickets)} requests, {toks} tokens, longest prompt "
          f"{longest}, {len(live)} live migration(s) "
          f"({', '.join(f'{m.rid} {m.src}->{m.dst} {m.wire_bytes} B' for m in live)}), "
          f"{wall:.1f} s wall incl. compiles")
    return fleet


def paged_logits(cfg, params, prompt, forced):
    """Logits of ``prompt``'s prefill and of one paged decode step per
    ``forced`` token, under the kernel backend current at call time
    (fresh jits, so each backend traces its own programs)."""
    import jax
    import jax.numpy as jnp
    from repro.models.layers import make_paged_attn_cache
    from repro.models.model import forward
    from repro.serving.paged import _weave

    n_pages = -(-(len(prompt) + len(forced)) // PAGE)
    table = jnp.arange(n_pages, dtype=jnp.int32)[None]   # one row

    def pools():
        one = make_paged_attn_cache(cfg, n_pages, PAGE)
        return [[{"attn": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (b.repeats,) + a.shape), one)}
            for _ in b.layers] for b in cfg.blocks]

    @jax.jit
    def prefill(params, caches, tokens):
        logits, caches, _ = forward(params, {"tokens": tokens}, cfg=cfg,
                                    mode="prefill",
                                    caches=_weave(caches, table))
        return logits[:, -1], caches

    @jax.jit
    def decode(params, caches, token, pos):
        logits, caches, _ = forward(params, {"tokens": token}, cfg=cfg,
                                    mode="decode", positions=pos,
                                    caches=_weave(caches, table))
        return logits[:, 0], caches

    rows = []
    t0 = time.perf_counter()
    row, caches = prefill(params, pools(), jnp.asarray(prompt)[None])
    rows.append(jax.block_until_ready(row))
    t_prefill = time.perf_counter() - t0
    for i, tok in enumerate(forced):
        pos = jnp.full((1, 1), len(prompt) + i, jnp.int32)
        t0 = time.perf_counter()
        row, caches = decode(params, caches,
                             jnp.full((1, 1), tok, jnp.int32), pos)
        rows.append(jax.block_until_ready(row))
        if i == 0:
            t_decode = time.perf_counter() - t0
    return (jnp.concatenate(rows).astype(jnp.float32)[:, :cfg.vocab_size],
            t_prefill, t_decode)


def oracle_gap(cfg, params):
    """Pallas kernels against the jnp oracle, same chip, same inputs."""
    import numpy as np
    from repro.kernels import ops
    rng = np.random.default_rng(SEED + 1)
    prompt = rng.integers(5, cfg.vocab_size, LONG_PROMPT).astype(np.int32)
    forced = rng.integers(5, cfg.vocab_size, ORACLE_STEPS).tolist()
    out = []
    for name in (None, "ref"):          # the kernels, then the oracle
        ops.set_backend(name)
        try:
            used = ops.backend()
            logits, t_pf, t_dec = paged_logits(cfg, params, prompt, forced)
        finally:
            ops.set_backend(None)
        out.append(logits)
        print(f"oracle phase, backend {used}: prefill[plen={LONG_PROMPT}] "
              f"first call {t_pf:.3f} s, decode first call {t_dec:.3f} s")
    kern, ref = out
    check(bool(np.isfinite(np.asarray(kern)).all()),
          "non-finite kernel logits")
    diff = abs(kern - ref)
    gap = float(diff.max())
    print(f"logit gap pallas vs oracle: max {gap:.6f} (tolerance "
          f"{LOGIT_TOL}), mean {float(diff.mean()):.6f}, max |logit| "
          f"{float(abs(ref).max()):.4f}, over prefill[{LONG_PROMPT}] + "
          f"{ORACLE_STEPS} paged decode steps")
    check(gap <= LOGIT_TOL,
          f"logit gap {gap} exceeds the bf16 tolerance {LOGIT_TOL}")


def one_chip(devs):
    fleet = serve(FLEET_ARGV)
    eng = next(iter(fleet.handles.values())).engine
    oracle_gap(eng.cfg, eng.params)
    stats = devs[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# four chips: cross-mesh migration
# ---------------------------------------------------------------------------

def four_chips(devs, cfg, params, *, max_len=64):
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.tree_util import keystr, tree_flatten_with_path
    from repro.core.migration import (deserialize_tree, place_tree,
                                      serialize_tree)
    from repro.core.workspace import AgentWorkspace
    from repro.launch.mesh import make_debug_mesh
    from repro.models.model import cache_specs
    from repro.serving.engine import Engine, Request

    n = 4
    mesh_a = make_debug_mesh(model=n, data=1)
    mesh_b = make_debug_mesh(model=1, data=n)
    eng = Engine(cfg, params, slots=4, max_len=max_len, seed=3, mesh=mesh_a)
    weights = sum(a.nbytes for a in jax.tree.leaves(params))
    for d in devs[:n]:
        used = (d.memory_stats() or {}).get("bytes_in_use", 0)
        print(f"device {d.id}: bytes_in_use {used}")
        check(used >= weights / n / 2,
              f"device {d.id} holds {used} B, under half its share of "
              f"{weights} B of weights")
    req = Request("r0", np.arange(6), max_new_tokens=10)
    eng.add_request(req)
    for _ in range(4):
        eng.step()
    pre = list(req.output)
    blob = serialize_tree(eng.state)
    print(f"donor on mesh (1, {n}): {len(pre)} tokens, state blob "
          f"{len(blob)} B")

    def restore(seed):
        eng2 = Engine(cfg, params, slots=4, max_len=max_len, seed=seed,
                      mesh=mesh_b)
        state = place_tree(deserialize_tree(
            blob, jax.eval_shape(lambda: eng2.state)))
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh_b, s),
            cache_specs(jax.eval_shape(lambda: eng2.state.caches), mesh_b))
        state = dataclasses.replace(
            state, caches=place_tree(state.caches, shardings))
        w = AgentWorkspace.from_engine(eng, "gid")
        w.engine_state = state
        return w.attach(eng2)

    def finish(e):
        out = []
        while e.requests:
            out += list(e.step().values())
        return out

    eng2 = restore(seed=99)
    fa, _ = tree_flatten_with_path(eng.state)
    fb, _ = tree_flatten_with_path(eng2.state)
    check(len(fa) == len(fb), "restored state has another structure")
    for (pa, la), (_, lb) in zip(fa, fb):
        if jax.dtypes.issubdtype(la.dtype, jax.dtypes.prng_key):
            la, lb = jax.random.key_data(la), jax.random.key_data(lb)
        check(np.array_equal(np.asarray(la), np.asarray(lb)),
              f"leaf {keystr(pa)} differs after the restore")
    post = finish(eng2)
    check(len(pre) + len(post) == 10, f"{len(pre)} + {len(post)} tokens")
    post2 = finish(restore(seed=1234))
    check(post == post2, f"restores diverged: {post} vs {post2}")
    print(f"restored on mesh ({n}, 1): {len(fa)} leaves equal, two restores "
          f"decoded identical continuations {post}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-mesh migration on 4 chips")
    args = ap.parse_args()
    devs = require_tpu(4 if args.four_chips else 1)

    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        import jax
        from repro.configs import get
        from repro.models.init import init_params
        cfg = get(ARCH)
        four_chips(devs, cfg, init_params(cfg, jax.random.key(SEED)))
    else:
        one_chip(devs)
    report(devs)


if __name__ == "__main__":
    main()
