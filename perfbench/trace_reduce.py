"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The reduction reads only what the profiler records:

* device operations: the ``XLA Ops`` line of each device plane
  (``/device:TPU:n``), each with its program (``XLA Modules`` line, or
  the op's ``hlo_module`` stat where the device plane has no module line);
* host spans: events on the host plane's threads, among them the
  benchmark's own ``jax.profiler.TraceAnnotation`` spans.

From those it gives device busy time (the union of op intervals),
device time per program and per kernel, and the longest idle gaps,
each named by the innermost host span that covers its middle.
Times are nanoseconds on the profiler's common timeline.
"""

from __future__ import annotations

import glob
import os
import warnings
from dataclasses import dataclass, field


@dataclass
class Op:
    name: str
    module: str
    start: float
    dur: float
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane -> [Op]
    host: list = field(default_factory=list)      # [Op] host spans

    def span(self, name: str) -> tuple[float, float] | None:
        """(start, end) of the first host span called ``name``."""
        for ev in self.host:
            if ev.name == name:
                return ev.start, ev.end
        return None


def _stats(ev) -> dict:
    out = {}
    try:
        for k, v in ev.stats:
            out[k] = v
    except (TypeError, ValueError):
        pass
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    with warnings.catch_warnings():
        # the event-stats iterator warns once per event on this JAX
        warnings.simplefilter("ignore", DeprecationWarning)
        return _load(path)


def _load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Op(e.name, "", e.start_ns, e.duration_ns,
                              _stats(e)) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [Op(e.name, e.name, e.start_ns, e.duration_ns,
                               _stats(e)) for e in line.events]
            _attach_modules(ops, mods)
            if ops:
                tr.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        tr.host.append(Op(e.name, line.name, e.start_ns,
                                          e.duration_ns, _stats(e)))
    tr.host.sort(key=lambda o: o.start)
    return tr


def _attach_modules(ops: list[Op], mods: list[Op]):
    """Name each op's program: its ``hlo_module`` stat, else the module
    event that contains it."""
    mods = sorted(mods, key=lambda m: m.start)
    j = 0
    for op in sorted(ops, key=lambda o: o.start):
        if op.stats.get("hlo_module"):
            op.module = str(op.stats["hlo_module"])
            continue
        while j < len(mods) and mods[j].end < op.start:
            j += 1
        if j < len(mods) and mods[j].start <= op.start:
            op.module = mods[j].name


def _clip(ops, t0, t1):
    for op in ops:
        s, e = max(op.start, t0), min(op.end, t1)
        if e > s:
            yield op, s, e


def merged(ops, t0: float, t1: float) -> list[tuple[float, float]]:
    """Union of the ops' intervals inside [t0, t1]."""
    iv = sorted((s, e) for _, s, e in _clip(ops, t0, t1))
    out: list[list[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(tr: Trace, t0: float, t1: float) -> float:
    """Device busy time in [t0, t1], averaged over the device planes."""
    if not tr.devices:
        return 0.0
    tot = sum(sum(e - s for s, e in merged(ops, t0, t1))
              for ops in tr.devices.values())
    return tot / len(tr.devices)


def time_where(tr: Trace, pred, t0: float, t1: float) -> float:
    """Device ns in [t0, t1] covered by ops for which ``pred(op)`` holds:
    the union of their intervals, so an op nested in another (a loop
    body's ops inside the loop) counts once; averaged over devices."""
    if not tr.devices:
        return 0.0
    tot = sum(sum(e - s for s, e in merged([o for o in ops if pred(o)],
                                            t0, t1))
              for ops in tr.devices.values())
    return tot / len(tr.devices)


def modules_with(tr: Trace, pred) -> set:
    """Names of the programs (XLA modules) that run an op matching
    ``pred``: programs are told apart by the kernels they call, since
    the trace may name every jitted partial alike."""
    return {op.module for ops in tr.devices.values() for op in ops
            if pred(op)}


def in_program(*names: str):
    """Predicate: an op of one of the programs (XLA modules) called
    ``names``, a jitted function's ``jit_<name>``; the trace adds the
    program's fingerprint in parentheses: ``jit__paged_decode_step(13...)``.
    """
    def pred(op: Op) -> bool:
        return op.module.split("(", 1)[0] in names
    return pred


def kernel(name: str):
    """Predicate: a Pallas kernel (``tpu_custom_call``) given ``name``
    (``pl.pallas_call(..., name=...)``): the trace names the op after it,
    ``%paged_decode_attention.9 = ...``."""
    def pred(op: Op) -> bool:
        head = op.name.split(" = ", 1)[0].lstrip("%")
        return ("tpu_custom_call" in op.name
                and head.rsplit(".", 1)[0] == name)
    return pred


def pallas_call(first_operand: str):
    """Predicate: a Pallas kernel (``tpu_custom_call``) whose first
    operand's type starts with ``first_operand``, e.g. ``"s32["`` for a
    kernel fed scalar-prefetch page tables, ``"bf16["`` for one fed
    activations.  The trace names these ops only by their HLO text."""
    def pred(op: Op) -> bool:
        return ("tpu_custom_call" in op.name
                and f"custom-call({first_operand}" in op.name)
    return pred


def operand_size(op: Op) -> int:
    """Element count of the op's first operand (``custom-call(s32[N]``)."""
    head = op.name.split("custom-call(", 1)[1]
    dims = head[head.index("[") + 1:head.index("]")]
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n


CONTAINERS = (" while(", " conditional(", " call(")


def short_name(op: Op) -> str:
    """An op's HLO name without its full text, e.g. ``%fusion.46``, with
    ``tpu_custom_call`` appended for a Pallas kernel."""
    head = op.name.split(" = ", 1)[0]
    return head + (" tpu_custom_call" if "tpu_custom_call" in op.name else "")


def is_container(op: Op) -> bool:
    """A loop, branch or call op, whose interval holds its body's ops."""
    return any(c in op.name for c in CONTAINERS)


def top_ops(tr: Trace, t0: float, t1: float, n: int = 10) -> list:
    """[(program/op, seconds)] of the leaf ops (loops and calls left out,
    their bodies counted) that took most device time."""
    acc: dict[str, float] = {}
    for ops in tr.devices.values():
        for op, s, e in _clip(ops, t0, t1):
            if is_container(op):
                continue
            key = f"{op.module}/{short_name(op)}"
            acc[key] = acc.get(key, 0.0) + (e - s)
    k = max(len(tr.devices), 1)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in top]


def idle_gaps(tr: Trace, t0: float, t1: float, n: int = 10) -> list:
    """The longest device idle gaps in [t0, t1] (first device plane),
    each named by the innermost host span covering its middle:
    [(host span name, seconds)]."""
    if not tr.devices:
        return []
    ops = next(iter(tr.devices.values()))
    gaps, cur = [], t0
    for s, e in merged(ops, t0, t1):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        cover = [h for h in tr.host if h.start <= mid <= h.end]
        name = (min(cover, key=lambda h: h.dur).name if cover
                else "no host span")
        out.append([name, (e - s) / 1e9])
    return out
