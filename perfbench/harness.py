"""One run of one cell: set-up, the measured window, metrics, the check.

The window drives the fleet's normal serving entry --
``FleetController.submit(RequestSpec)`` then ``FleetController.step()``
-- over one ``PagedEngine`` with the prefix cache armed, from one
process and one thread.  Requests are timed from when they were due, by
the host clock read after the ``step()`` that committed each token.

Set-up is timed from process start to the opening of the window:
weights, engine, prefix fill, one request per row and length so every
program the window can reach has run once, and then the traffic's own
ramp (``ramp_s``), so that the window opens with every session already
in flight.  After the window the program's state is freed and the
float32 reference judges a sample of the requests the window finished.
"""

from __future__ import annotations

import contextlib
import gc
import heapq
import shutil
import time
from dataclasses import dataclass
from pathlib import Path

import jax

import cells
import flops
import loadgen
import sut
import trace_reduce
from reference import check

HERE = Path(__file__).resolve().parent
TRACE_DIR = HERE / ".trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Ctx:
    """What a metric reader sees of one run."""
    conf: dict
    seconds: float
    t0: float                        # window opens (host perf_counter)
    t1: float                        # window closes
    setup_s: float
    setup_programs: int
    reqs: list                       # every loadgen.Req submitted, ramp too
    admissions: list                 # (t, prompt tokens, cached tokens)
    decodes: list                    # (t, [keys attended per active row])
    device_kind: str
    max_len: int                     # the engine's context budget
    serving: dict                    # the configuration's serving group
    trace: object = None             # trace_reduce.Trace of the window
    trace_span: tuple = ()           # (start, end) ns of the window

    def peak(self) -> dict:
        return flops.peak(self.device_kind)

    def inside(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


class _Compiles:
    """Counts XLA executables made ready (compiled or read back from the
    persistent cache) through JAX's monitoring events."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def _annotate(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def _submit(fleet, r: loadgen.Req, now: float):
    r.t_submit = now
    r.ticket = fleet.submit(sut.RequestSpec(
        prompt=r.prompt, rid=r.rid, max_new_tokens=r.max_new,
        tenant=r.tenant))
    return r.ticket is not None


class Bench:
    """The system under test, built and warmed: weights, one engine with
    the prefix cache armed behind the fleet, every program it will run
    made ready.  Records each admission and decode step from outside the
    engine."""

    def __init__(self, *, config_name: str, conf: dict, traffic_spec: dict,
                 seed: int, clock, annotate: bool, tamper=None):
        self.clock, self.annotate = clock, annotate
        self.admissions: list = []
        self.decodes: list = []
        self.phases: dict = {}
        self.cfg = sut.model_config(config_name, conf)
        self.params = sut.program_params(conf, self.cfg, seed)
        jax.block_until_ready(self.params)
        self.phases["weights"] = clock()
        self.serving = serving = conf["serving"]
        ps = serving["page_size"]
        warm = loadgen.Traffic(traffic_spec, seed, conf["vocab_size"])
        self.max_len = -(-warm.max_tokens() // ps) * ps
        self.fleet, self.engine = sut.serve(self.cfg, self.params, serving,
                                            self.max_len)
        if tamper is not None:
            tamper(self.engine)
        self._instrument()
        jax.block_until_ready(self.engine.state)
        self.phases["engine"] = clock()
        self.prefix = warm.prefix        # the tenant prompts cached at set-up
        self._warm(warm.warm_phases(serving["rows"]))
        jax.block_until_ready(self.engine.state)
        self.phases["warm"] = clock()

    def _instrument(self):
        engine, clock, on = self.engine, self.clock, self.annotate
        add, step = engine.add_request, engine.step

        def add_request(req, **kw):
            with _annotate("bench.admit", on):
                ok = add(req, **kw)
            if ok:
                self.admissions.append((clock(), len(req.prompt),
                                        engine.last_prefix_hit))
            return ok

        def decode(**kw):
            ctx = [len(r.prompt) + len(r.output) + 1
                   for r in engine.requests.values() if not r.done]
            with _annotate("bench.decode", on):
                out = step(**kw)
            self.decodes.append((clock(), ctx))
            return out

        engine.add_request, engine.step = add_request, decode

    def _warm(self, phases):
        for phase in phases:
            for r in phase:
                if not _submit(self.fleet, r, self.clock()):
                    raise RuntimeError(f"warm-up request {r.rid} refused")
            for _ in range(10_000):
                if all(r.ticket.done for r in phase):
                    break
                self.fleet.step()
            bad = [r.rid for r in phase if r.ticket.state.value != "done"]
            if bad:
                raise RuntimeError(f"warm-up requests did not finish: {bad}")

    def serve(self, traffic: loadgen.Traffic, seconds: float,
              trace: bool) -> tuple[float, float, list]:
        """Serve the traffic's ramp, then the window of ``seconds``
        (traced if asked); returns (t0, t1, every submitted request)."""
        clock, fleet = self.clock, self.fleet
        pending = [(r.due, r.rid, r) for r in traffic.initial(seconds)]
        heapq.heapify(pending)
        live: dict[str, loadgen.Req] = {}
        submitted: list[loadgen.Req] = []
        tb = clock()

        def until(t_end):
            while True:
                now = clock()
                if now >= t_end:
                    return
                while pending and tb + pending[0][0] <= now:
                    r = heapq.heappop(pending)[2]
                    r.t_due = tb + r.due
                    submitted.append(r)
                    if _submit(fleet, r, now) and not r.ticket.done:
                        live[r.rid] = r
                if not live:
                    wake = tb + pending[0][0] if pending else t_end
                    with _annotate("bench.idle", self.annotate):
                        time.sleep(max(0.0, min(wake, t_end) - clock()))
                    continue
                with _annotate("bench.step", self.annotate):
                    emitted = fleet.step()
                t = clock()
                for rid in emitted:
                    if rid in live:
                        live[rid].t_tokens.append(t)
                for rid, r in list(live.items()):
                    if r.ticket.done:
                        r.t_done, r.output = t, list(r.ticket.output)
                        del live[rid]
                        for nxt in traffic.after(r, t - tb):
                            heapq.heappush(pending, (nxt.due, nxt.rid, nxt))

        with _annotate("bench.ramp", self.annotate):
            until(tb + traffic.ramp_s)
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, not every call
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        t0 = clock()
        with _annotate("bench.window", self.annotate):
            until(t0 + seconds)
        self.live = live
        return t0, t0 + seconds, submitted


def device_info(devices) -> dict:
    d = devices[0]
    peaks = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def judge(conf: dict, sample: list, exact: list, chosen: list) -> dict:
    """The check: the widest gap of the chosen tokens below the float32
    reference's best (``exact``: its logits at the rows that chose each
    served token), against the configuration's limit."""
    gap = check.chosen_gap(exact, chosen) if sample else None
    limit = conf["check"]["served_logit_gap"]
    return {"correct": gap is not None and gap <= limit,
            "checks": {"served_logit_gap": {"value": gap, "limit": limit}}}


def run(*, config_name: str, conf: dict, traffic_spec: dict,
        seed: int, seconds: float, trace: bool, metrics: list[dict],
        t_start: float, devices, tamper=None, control: bool = False,
        keep: dict | None = None) -> dict:
    """One run; returns the result object (the last line's content).

    ``tamper(engine)`` lets a test break the timed path underneath;
    ``control`` puts the reference computed in fp8 in the program's place
    for the check; ``keep``, where given, receives the compared sample,
    the reference's logits and the sizes."""
    clock = time.perf_counter
    t_enter = clock()
    with _Compiles() as compiles:
        bench = Bench(config_name=config_name, conf=conf,
                      traffic_spec=traffic_spec, seed=seed, clock=clock,
                      annotate=trace, tamper=tamper)
        traffic = loadgen.Traffic(traffic_spec, seed, conf["vocab_size"])
        setup_programs = compiles.n
        t0, t1, reqs = bench.serve(traffic, seconds, trace)
        window_compiles = compiles.n - setup_programs
        jax.block_until_ready(bench.engine.state)
    phases = {k: v - t_start for k, v in
              dict(start=t_enter, **bench.phases).items()}
    tr, span = None, ()
    if trace:
        jax.profiler.stop_trace()
        tr = trace_reduce.load(trace_reduce.find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        span = tr.span("bench.window") or ()
    device = device_info(devices)
    ctx = Ctx(conf=conf, seconds=seconds, t0=t0, t1=t1,
              setup_s=t0 - t_start, setup_programs=setup_programs,
              reqs=reqs, device_kind=device["kind"],
              admissions=[a for a in bench.admissions if t0 <= a[0] <= t1],
              decodes=[d for d in bench.decodes if t0 <= d[0] <= t1],
              max_len=bench.max_len, serving=bench.serving,
              trace=tr, trace_span=span)
    values = {}
    for m in metrics:
        v = cells.metric_reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    if trace and tr is not None and span:
        device["busy_s"] = trace_reduce.busy_ns(tr, *span) / 1e9
        device["window_s"] = (span[1] - span[0]) / 1e9

    due = [r for r in reqs if t0 <= r.t_due <= t1]
    failed = [r for r in due if r.ticket is None or (
        r.ticket.done and r.ticket.state.value != "done")]
    done = [r for r in reqs if r.t_done is not None and ctx.inside(r.t_done)
            and r.ticket.state.value == "done"]
    # free the program's state before the reference runs: tickets hold
    # the fleet, the fleet holds the engine and its page pools
    cfg, max_len = bench.cfg, bench.max_len
    for r in reqs:
        r.ticket = None
    del bench, ctx
    gc.collect()
    t_check = clock()
    sample = check.sample(done, seed)
    exact, chosen = [], []
    if sample:
        ref = cells.reference(conf)
        seqs, rows = map(list, zip(*(check.rows_of(r) for r in sample)))
        exact = ref.logits_at(conf, seed, seqs, rows)
        chosen = ([lg.argmax(-1) for lg in
                   ref.logits_at(conf, seed, seqs, rows, quant="fp8")]
                  if control else [r.output for r in sample])
    verdict = judge(conf, sample, exact, chosen)
    if keep is not None:
        keep.update(sample=sample, exact=exact, cfg=cfg, max_len=max_len)
    result = {
        "correct": verdict["correct"],
        "attempted": len(due),
        "failed": len(failed),
        "metrics": values,
        "device": device,
    }
    if trace and tr is not None and span:
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr, *span),
            "idle_gaps": trace_reduce.idle_gaps(tr, *span),
        }
    result["info"] = {
        "setup_phases_s": phases,
        "window_compiles": window_compiles, "setup_programs": setup_programs,
        "finished": len(done), "sampled": len(sample),
        "sampled_tokens": sum(len(r.output) for r in sample),
        "check_s": clock() - t_check,
        "max_len": max_len, "kv_pages": conf["serving"]["kv_pages"],
        "generator_late_ms_max": 1e3 * max(
            [r.t_submit - r.t_due for r in reqs
             if r.t_submit is not None] or [0.0]),
    }
    result["checks"] = verdict["checks"]
    return result
