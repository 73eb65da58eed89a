"""Run one cell of ``BENCHMARK.json`` once and print its result line.

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  The run:

1. fails, before any work and with no result, where JAX finds no TPU or
   fewer chips than the cell asks for;
2. set-up: draws the weights from the seed on the device, builds the
   program's ``DisaggregatedCluster`` from the configuration and runs every
   prompt, resume and decode shape the mix can produce once through the
   engines (the persistent compile cache at ``.jax_cache/`` in the
   checkout makes this a load after a checkout's first run);
3. drives the mix through ``DisaggregatedCluster.submit`` and ``step``: a
   warm span that is not counted, then the measured window of ``--seconds``,
   then a drain for the requests due in the window;
4. frees the program's state and compares a sample of finished requests,
   drawn from the seed with the longest among them, with the float32
   reference of the configuration's architecture module
   (``bench/<reference>.py``, ``bench/model_ref.py`` where the
   configuration names none): ``correct`` holds where no served token's
   reference logit lies further below the reference's best than the
   configuration's limit.

The architecture module also draws the weights (step 2) and counts a step's
work for the readers (``ctx["work"]``).  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read by
``bench/metrics/<metric>.py`` from host spans over the window, a profiler
trace of its last seconds and the program's counters at the window's
edges; a reader that declares ``NEEDS = ("program",)`` also gets the
program's own spans, with its tracer on for that run only.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import re
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import loadgen, spans as spans_mod, stats, tracefile

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
SAMPLE = 8            # finished requests compared with the reference
TRACE_S = 4.0         # profiler sub-window: the window's last seconds
DRAIN_S = 60.0        # drain limit for requests due in the window
GRID = 128


class NoChip(RuntimeError):
    pass


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell ----

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: List[dict], name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no entry named {name!r}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(bench: dict, cell: str):
    """The cell's end-to-end metrics, and its per-layer metrics (those that
    list it, or that list no cells and move one of its end-to-end ones)."""
    e2e = [m for m in bench["end_to_end"] if applies(m, cell)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, directory: Path = BENCH / "metrics"):
    """The reader module of ``metric``: ``<directory>/<base>.py``, where a
    suffix after the first ``.`` names a variant of the same reader."""
    base = metric.split(".", 1)[0]
    return load_module(directory / f"{base}.py", f"bench_metric_{base}")


def architecture(cfgdoc: dict, directory: Path = BENCH):
    """The configuration's architecture module: ``<directory>/<name>.py``
    for ``"reference": "<name>"``, ``model_ref`` where it names none.  It
    draws the weights, judges ``correct``, lists the control's types and
    counts a step's work (see ``bench/model_ref.py``)."""
    name = cfgdoc.get("reference", "model_ref")
    return load_module(directory / f"{name}.py", f"bench_arch_{name}")


def check_controls(control: Sequence[str], arch) -> None:
    bad = [q for q in control if q not in arch.QUANTS]
    if bad:
        raise SystemExit(f"bench: no control type {bad[0]!r}; the "
                         f"configuration's reference has "
                         f"{','.join(arch.QUANTS)}")


def check_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Executables built (compiled, or loaded from the persistent cache)
    and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.built = 0
        self.hits = 0
        self.compile_s = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.built += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        self._listeners = (on_duration, on_event)

    def snapshot(self):
        return self.built, self.hits, self.compile_s

    def close(self) -> None:
        import jax.monitoring as mon
        on_duration, on_event = self._listeners
        mon.unregister_event_duration_listener(on_duration)
        mon.unregister_event_listener(on_event)


# ----------------------------------------------------------- the model ----

def model_config(base, fields: dict):
    """The registry's ``ModelConfig`` with the configuration's ``model``
    fields set.  A dict sets the fields of a nested dataclass (``moe``,
    ``ssm``, ``xlstm``) over the registry's own; a key that no dataclass
    has, or a dict for a field the registry leaves ``None``, is an error."""
    known = {f.name for f in dataclasses.fields(base)}
    out = {}
    for k, v in fields.items():
        if k not in known:
            raise KeyError(f"configuration key model.{k}: ModelConfig has "
                           f"no field {k!r}")
        if isinstance(v, dict):
            cur = getattr(base, k)
            if not dataclasses.is_dataclass(cur):
                raise ValueError(f"configuration key model.{k}: a group is "
                                 f"given, but {base.name!r} has {k}={cur!r}")
            sub = {f.name for f in dataclasses.fields(cur)}
            for kk in v:
                if kk not in sub:
                    raise KeyError(f"configuration key model.{k}.{kk}: "
                                   f"{type(cur).__name__} has no field "
                                   f"{kk!r}")
            v = dataclasses.replace(cur, **v)
        out[k] = v
    return dataclasses.replace(base, **out)


def build_cluster(cfgdoc: dict, params, seed: int):
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serving.disagg import DisaggregatedCluster
    cfg = model_config(get_config(cfgdoc["registry"]), cfgdoc["model"])
    dep = cfgdoc["deployment"]
    return DisaggregatedCluster(
        Model(cfg), params, num_decode=dep["num_decode"],
        slots_per_worker=dep["slots_per_worker"], max_len=dep["max_len"],
        prefill_cache_entries=dep["prefill_cache_entries"],
        max_prefill_batch=dep["max_prefill_batch"], seed=seed)


def program_counters(cluster) -> Dict[str, float]:
    """The values of the cluster's ``MetricsRegistry`` counters, by name."""
    from repro.core.metrics import Counter
    return {name: c.value for name, c in cluster.metrics._metrics.items()
            if isinstance(c, Counter)}


def widths(max_batch: int) -> List[int]:
    w, out = 1, []
    while w <= max_batch:
        out.append(w)
        w *= 2
    if out[-1] < max_batch:
        out.append(out[-1] * 2)
    return out


def warm_up(cluster, shapes: dict, vocab: int, rng) -> int:
    """Run every shape the mix can produce once through the engines, as
    the window will: cold passes per (width, prompt length), resumes per
    (width, suffix length), each admitted into every decoder and stepped.
    The prefix cache is emptied afterwards and the control plane is not
    touched.  Returns the number of prompt passes run."""
    pf, decs = cluster.prefill, cluster.decoders
    ws = widths(pf.max_batch)
    passes = 0

    def rand(n):
        return rng.integers(0, vocab, size=n).tolist()

    def admit_all(outs, lens):
        for dec in decs:
            for i, ((logits, caches, row), n) in enumerate(zip(outs, lens)):
                dec.admit(dec.free_slot(), f"warm{i}", caches,
                          int(np.argmax(logits)), prompt_len=n, max_new=1,
                          src_row=row)
            while dec.active_count:
                dec.step()

    for n in shapes["prompt_lengths"]:
        for w in ws:
            outs = pf.prefill_many([(rand(n), None, None) for _ in range(w)])
            admit_all(outs, [n] * w)
            passes += 1
    if shapes["resume_suffixes"] and pf.model.supports_prefill_resume \
            and pf.cache_entries > 0:
        start = GRID
        base = rand(start)
        for s in shapes["resume_suffixes"]:
            for w in ws:
                donor = base + rand(min(shapes["prompt_lengths"]) - start)
                pf.prefill_many([(donor, None, None)])
                prompts = [base + rand(s) for _ in range(w)]
                outs = pf.prefill_many([(p, None, None) for p in prompts])
                admit_all(outs, [start + s] * w)
                passes += 2
    pf.clear_cache()
    return passes


def decode_kernels(dec) -> dict:
    """The decode step's compiled program, as the profiler's trace names
    it: its module and its Mosaic kernel calls (``tpu_custom_call``
    instructions).  Asked of the program itself, so that a renamed step or
    kernel is still found; the executable comes from the compile cache."""
    import jax.numpy as jnp
    kw = {"page_table": jnp.asarray(dec.page_table)} if dec.paged else {}
    return kernel_names(dec._decode.lower(
        dec.params, dec.caches, jnp.asarray(dec.tokens),
        jnp.zeros((dec.num_slots,), jnp.int32), **kw).compile().as_text())


def kernel_names(hlo: str) -> dict:
    """Module name and ``tpu_custom_call`` instruction names of a compiled
    program's HLO text."""
    module = re.search(r"^HloModule ([^\s,]+)", hlo, re.M)
    ops = re.findall(r"^\s*(?:ROOT )?%?([^\s=]+) = [^\n]*"
                     r'custom_call_target="tpu_custom_call"', hlo, re.M)
    return {"module": module.group(1) if module else None,
            "ops": sorted(set(ops))}


# --------------------------------------------------------- the traffic ----

@dataclass
class Track:
    req: loadgen.Req
    serve: object                  # the program's ServeRequest
    due: float                     # benchmark clock
    submit: float
    times: List[float] = field(default_factory=list)   # each output token
    done: bool = False


class Driver:
    """Submits the mix, steps the cluster and stamps every token."""

    def __init__(self, cluster, clock, spans=None):
        from repro.serving.disagg import ServeRequest
        self.ServeRequest = ServeRequest
        self.cluster = cluster
        self.clock = clock
        self.spans = spans
        self.tracks: List[Track] = []
        self.inflight: Dict[str, Track] = {}
        self.offset: Optional[float] = None   # benchmark clock - cluster's

    def submit(self, req: loadgen.Req, due: Optional[float] = None) -> Track:
        sr = self.ServeRequest(req.rid, list(req.tokens),
                               max_new_tokens=req.out_len - 1)
        now = self.clock()
        self.cluster.submit(sr)
        if self.offset is None:
            self.offset = now - sr.submit_t
        tr = Track(req, sr, now if due is None else due, now)
        self.tracks.append(tr)
        self.inflight[req.rid] = tr
        return tr

    def tick(self) -> List[Track]:
        self.cluster.step()
        done = []
        for rid, tr in list(self.inflight.items()):
            sr = tr.serve
            n = len(sr.output)
            if n > len(tr.times):
                if not tr.times:
                    tr.times.append(sr.first_token_t + self.offset)
                while len(tr.times) < n:
                    tr.times.append(sr.last_token_t + self.offset)
            if sr.finish_t > 0.0:
                tr.done = True
                del self.inflight[rid]
                done.append(tr)
        return done

    def idle(self) -> bool:
        return not (self.cluster.pending or self.cluster.running)

    def sleep(self, seconds: float) -> None:
        if seconds <= 0:
            return
        if self.spans is not None:
            self.spans.sleep(seconds)
        else:
            time.sleep(seconds)


class TraceWindow:
    """The profiler over [start, end) of the benchmark clock, opened and
    closed between ticks."""

    def __init__(self, start: float, end: float):
        self.start, self.end = start, end
        self.dir = None
        self.ann = None
        self.t = None     # (start, end) actually traced, benchmark clock

    def poll(self, now: float) -> None:
        import jax
        if self.dir is None and now >= self.start:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            # no Python tracer: it would slow every host span it covers
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench:trace_start"):
                pass   # the first annotation of a session pays its set-up
            self.ann = jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN)
            self.ann.__enter__()
            self.t = [now, None]
        elif self.ann is not None and now >= self.end:
            self.close(now)

    def close(self, now: float) -> None:
        import jax
        if self.ann is None:
            return
        self.ann.__exit__(None, None, None)
        self.ann = None
        self.t[1] = now
        jax.profiler.stop_trace()

    def read(self) -> Optional[dict]:
        if self.dir is None:
            return None
        try:
            found = sorted(Path(self.dir).rglob("*.xplane.pb"))
            return tracefile.load(str(found[-1])) if found else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def drive_open(drv: Driver, schedule: List[loadgen.Req], w0: float, w1: float,
               drain_s: float, trace: Optional[TraceWindow] = None) -> None:
    """Open loop: each request is submitted once its due time has come,
    whatever the server's state; the loop ends when every request due in
    [w0, w1) has finished, or at the drain limit."""
    i = 0
    window = {r.rid for r in schedule if w0 <= r.due < w1}
    while True:
        now = drv.clock()
        if trace is not None:
            trace.poll(now)
        while i < len(schedule) and schedule[i].due <= now:
            drv.submit(schedule[i], due=schedule[i].due)
            i += 1
        if now >= w1:
            if trace is not None:
                trace.close(now)
            if now >= w1 + drain_s or not any(rid in window
                                              for rid in drv.inflight):
                return
        if drv.idle():
            if i < len(schedule):
                drv.sleep(schedule[i].due - now)
            else:
                drv.sleep(min(w1 - now, 0.05))
            continue
        drv.tick()


def drive_closed(drv: Driver, gen: loadgen.ClosedSessions, w1: float,
                 drain_s: float, trace: Optional[TraceWindow] = None) -> None:
    """Closed loop: every client has one request in flight and sends its
    next as soon as the last one finishes, until the window closes; then
    the requests in flight drain."""
    for c in range(gen.n_clients):
        drv.submit(gen.next_request(c))
    while drv.inflight:
        now = drv.clock()
        if trace is not None:
            trace.poll(now)
            if now >= w1:
                trace.close(now)
        if now >= w1 + drain_s:
            return
        for tr in drv.tick():
            if drv.clock() < w1:
                drv.submit(gen.next_request(tr.req.client))


# ------------------------------------------------------------- results ----

def e2e_value(name: str, tracks: List[Track], w0: float, w1: float,
              deadline: float, setup_s: float) -> float:
    """The end-to-end metrics, by name: ``setup_s``, ``ttft_p<q>_ms`` and
    ``itl_p<q>_ms`` (percentiles over every request due in the window and
    every token gap ending in it), ``output_tok_s`` (tokens emitted in the
    window over its length)."""
    if name == "setup_s":
        return setup_s
    if name == "output_tok_s":
        n = sum(1 for tr in tracks for t in tr.times if w0 <= t < w1)
        return n / (w1 - w0)
    m = re.fullmatch(r"(ttft|itl)_p(\d+(?:\.\d+)?)_ms", name)
    if not m:
        raise KeyError(f"no end-to-end metric named {name!r}")
    q = float(m.group(2))
    if m.group(1) == "ttft":
        vals = [((tr.times[0] if tr.times else deadline) - tr.due) * 1e3
                for tr in tracks if w0 <= tr.due < w1]
    else:
        vals = [(b - a) * 1e3 for tr in tracks
                for a, b in zip(tr.times, tr.times[1:]) if w0 <= b < w1]
    return stats.percentile(vals, q)


def sample_requests(tracks: List[Track], w0: float, w1: float, seed: int,
                    k: int = SAMPLE) -> List[Track]:
    """``k`` finished requests due in the window, drawn from the seed, with
    the longest (prompt and output) among them."""
    pool = sorted((tr for tr in tracks if tr.done and w0 <= tr.due < w1),
                  key=lambda tr: tr.req.rid)
    if not pool:
        return []
    longest = max(pool, key=lambda tr: len(tr.req.tokens) + len(tr.serve.output))
    rest = [tr for tr in pool if tr is not longest]
    pick = loadgen.rng_for(seed, 9).permutation(len(rest))[:k - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def ref_sizes(mix: dict) -> tuple:
    shapes = loadgen.prompt_shapes(mix)
    p_pad = int(mix["output"]["max"])
    t = max(shapes["prompt_lengths"]) + p_pad
    return int(math.ceil(t / GRID) * GRID), p_pad


# ----------------------------------------------------------------- run ----

def run_cell(bench: dict, cell: dict, cfgdoc: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float, devs,
             control: Sequence[str] = (),
             fault=None, drain_s: float = DRAIN_S,
             bench_dir: Path = BENCH) -> dict:
    import jax

    m = cfgdoc["model"]
    arch = architecture(cfgdoc, bench_dir)
    check_controls(control, arch)
    counter = CompileCounter()
    params = jax.block_until_ready(arch.init_params(m, seed))
    cluster = build_cluster(cfgdoc, params, seed)
    if fault is not None:
        fault(cluster)
    shapes = loadgen.prompt_shapes(mix)
    t_w = time.monotonic()
    passes = warm_up(cluster, shapes, m["vocab_size"],
                     loadgen.rng_for(seed, 8))
    warm_s = time.monotonic() - t_w
    log(f"set-up: weights and {passes} warm-up prompt passes; warm-up "
        f"{warm_s!r} s; executables built {counter.built} "
        f"(persistent-cache hits {counter.hits}, compile "
        f"{counter.compile_s!r} s)")
    e2e, layer = cell_metrics(bench, cell["name"])
    readers = {met["name"]: reader(met["name"], bench_dir / "metrics")
               for met in layer} if trace else {}
    if any("program" in getattr(r, "NEEDS", ()) for r in readers.values()):
        cluster.tracer.enable()

    kernels = None
    if trace:
        try:
            kernels = decode_kernels(cluster.decoders[0])
        except Exception:   # the roofline goes silent; the run goes on
            log("decode kernels not found:\n" + traceback.format_exc())
    sp = None
    t0 = time.monotonic()
    setup_s = t0 - t_start
    if trace:
        sp = spans_mod.Spans(t0)
        spans_mod.instrument(cluster, sp)
    drv = Driver(cluster, lambda: time.monotonic() - t0, sp)
    w0 = float(mix["warm_s"])
    w1 = w0 + float(seconds)
    tw = TraceWindow(max(w0, w1 - TRACE_S), w1) if trace else None
    occupancy: List[tuple] = []
    edges: Dict[str, tuple] = {}

    def counters():
        # prefill counters and the program's registry counters
        return (dataclasses.replace(cluster.prefill.stats),
                program_counters(cluster))

    def at_window_edges(now_fn):
        # counters are read at the window's edges, occupancy inside it
        orig = drv.tick

        def tick():
            now = now_fn()
            if "start" not in edges and now >= w0:
                edges["start"] = counters()
            if now < w1:
                if now >= w0:
                    occupancy.append(tuple(d.active_count
                                           for d in cluster.decoders))
            elif "end" not in edges:
                edges["end"] = counters()
            return orig()
        drv.tick = tick

    at_window_edges(drv.clock)
    built0 = counter.snapshot()
    if mix["loop"] == "open":
        schedule = loadgen.open_schedule(mix, seed, m["vocab_size"], w1)
        drive_open(drv, schedule, w0, w1, drain_s, tw)
    else:
        n = loadgen.clients(mix, cfgdoc["deployment"])
        gen = loadgen.ClosedSessions(mix, seed, m["vocab_size"], n)
        drive_closed(drv, gen, w1, drain_s, tw)
    built1 = counter.snapshot()
    counter.close()
    compiles_in_window = built1[0] - built0[0]
    edges.setdefault("start", counters())
    edges.setdefault("end", counters())
    deadline = w1 + drain_s
    tracks = drv.tracks
    if mix["loop"] == "open":
        attempted = [tr for tr in tracks if w0 <= tr.due < w1]
    else:
        attempted = [tr for tr in tracks if w0 <= tr.submit < w1]
    failed = sum(1 for tr in attempted if not tr.done)
    dinfo = device_info(devs)
    tr_data = tw.read() if tw is not None else None

    result: dict = {"correct": False, "attempted": len(attempted),
                    "failed": failed, "metrics": {}, "device": dinfo}
    if trace:
        ctx = {"spans": sp, "window": (w0, w1),
               "traced": tuple(tw.t) if tw and tw.t else None,
               "prefill": (edges["start"][0], edges["end"][0]),
               "counters": (edges["start"][1], edges["end"][1]),
               "occupancy": occupancy,
               "slots": sum(d.num_slots for d in cluster.decoders),
               "model": m, "work": arch, "device_kind": dinfo["kind"],
               "trace": tr_data, "tracks": tracks, "decode_kernels": kernels}
        if cluster.tracer.enabled:
            ctx["program"] = spans_mod.ProgramSpans(cluster.tracer.spans, t0)
        for met in layer:
            base, _, variant = met["name"].partition(".")
            v = readers[met["name"]].read(ctx, variant or None)
            if v is not None:
                result["metrics"][met["name"]] = {"value": v,
                                                  "unit": met["unit"]}
        b = tracefile.busy(tr_data) if tr_data else {}
        if b:
            dinfo["busy_s"] = b["busy_s"]
            dinfo["window_s"] = b["window_s"]
            result["breakdown"] = tracefile.breakdown(tr_data)
    else:
        for met in e2e:
            result["metrics"][met["name"]] = {
                "value": e2e_value(met["name"], tracks, w0, w1, deadline,
                                   setup_s), "unit": met["unit"]}

    # ---- correctness: free the program, then the reference ----
    sample = sample_requests(tracks, w0, w1, seed)
    seqs = [(list(tr.req.tokens), list(tr.serve.output)) for tr in sample]
    del cluster, drv, tracks, attempted, sample
    gc.collect()
    t_ref = time.monotonic()
    limit = float(cfgdoc["correct"]["max_logit_gap"])
    checks: dict = {}
    if seqs:
        t_pad, p_pad = ref_sizes(mix)
        g = arch.served_gaps(params, m, seqs, t_pad, p_pad, control)
        # each control takes the program's place: its gap is the one judged
        if control:
            checks["program_max_logit_gap"] = {"value": g["served"],
                                               "limit": limit}
            for q in control:
                checks[f"{q}_max_logit_gap"] = {"value": g["control"][q],
                                                "limit": limit}
            gap = max(g["control"].values())
        else:
            gap = g["served"]
        checks["max_logit_gap"] = {"value": gap, "limit": limit}
        checks["served_tokens_compared"] = {"value": g["tokens"],
                                            "limit": "> 0"}
        in_vocab = all(0 <= t < m["vocab_size"] for _, out in seqs for t in out)
        result["correct"] = bool(gap <= limit and g["tokens"] > 0
                                 and in_vocab)
    log(f"reference: {len(seqs)} requests in {time.monotonic() - t_ref!r} s")
    log(f"compiles_in_window={compiles_in_window} "
        f"setup_s={setup_s!r} warm_up_s={warm_s!r} "
        f"memory_peak_bytes={dinfo['memory_peak_bytes']}")
    result["compiles_in_window"] = compiles_in_window
    result["checks"] = checks
    return result


# ----------------------------------------------------------------- cli ----

def quant_list(text: str) -> tuple:
    return tuple(q for q in text.split(",") if q)


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default="", type=quant_list,
                   help="the control: judge the reference with weights "
                        "rounded to this type in the program's place; a "
                        "comma list judges each (the types in QUANTS of the "
                        "configuration's architecture module)")
    return p.parse_args(argv)


def load_cell(root: Path, workload: str):
    """``BENCHMARK.json`` under ``root``, the cell named ``workload``, its
    configuration and its traffic mix, each found by name."""
    bench = load_json(root / "BENCHMARK.json")
    cell = find(bench["workloads"], workload)
    cfgdoc = load_json(root / "bench" / "configs" / f"{cell['config']}.json")
    mix = loadgen.load_mix(cell["traffic"], root / "bench" / "traffic")
    return bench, cell, cfgdoc, mix


def main(argv, t_start: float, root: Path = ROOT) -> int:
    args = parse_args(argv)
    bench, cell, cfgdoc, mix = load_cell(root, args.workload)
    try:
        devs = check_device(int(cell["chips"]))
    except NoChip as e:
        log(f"bench: {e}; no result")
        return 3
    import repro  # noqa: F401  (the program under test must be present)
    enable_compile_cache()
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} cell={cell['name']} seed={args.seed}")
    res = run_cell(bench, cell, cfgdoc, mix, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   t_start=t_start, devs=devs,
                   control=args.control, bench_dir=root / "bench")
    for k, v in res["checks"].items():
        log(f"check {k}: {v['value']!r} limit {v['limit']}")
    print(json.dumps(res), flush=True)
    return 0
