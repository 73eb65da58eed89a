"""The host tracer of the served path (``repro.core.tracing``) and the spans
and counters the engine backend records with it."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.core import tracing
from repro.core.tracing import OFF, Span, Tracer, exclusive_seconds
from repro.models import build_model
from repro.serving.disagg import DisaggregatedCluster, ServeRequest
from repro.serving.workload import template_tokens


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _no_clock():
    raise AssertionError("the clock was read")


def test_disabled_tracer_records_nothing_and_reads_no_clock(monkeypatch):
    monkeypatch.setattr(tracing.time, "monotonic", _no_clock)
    tr = Tracer()
    a = tr.span("tick")
    b = tr.span("route", rid="r0", detail=3)
    assert a is OFF and b is OFF          # one shared context, no allocation
    with a as sp:
        sp.detail = 5                     # dropped
        with b:
            pass
    tr.record("queue", 1.0, 2.0, rid="r0")
    assert tr.spans == [] and OFF.detail is None


def test_timed_reads_the_clock_on_and_off(monkeypatch):
    monkeypatch.setattr(tracing.time, "monotonic", FakeClock())
    tr = Tracer()
    with tr.timed("route.select") as sw:
        pass
    assert sw.seconds == 1.0 and tr.spans == []
    tr.enable()
    with tr.timed("route.select") as sp:
        pass
    assert sp.seconds == 1.0 and tr.spans == [sp]


def test_enabled_tracer_nests_parents_and_computes_self_time(monkeypatch):
    monkeypatch.setattr(tracing.time, "monotonic", FakeClock())
    tr = Tracer(enabled=True)
    with tr.span("tick"):                             # 1 .. 12
        with tr.span("schedule", detail=2):           # 2 .. 9
            with tr.span("route", rid="a"):           # 3 .. 4
                pass
            with tr.span("prefill.pass") as sp:       # 5 .. 8
                with tr.span("hop"):                  # 6 .. 7 (inside)
                    pass
                sp.detail = {"kind": "cold"}
        with tr.span("hop", rid="a"):                 # 10 .. 11
            pass
    tr.record("queue", 0.5, 2.5, rid="a", detail=1)
    names = [s.name for s in tr.spans]
    assert names == ["tick", "schedule", "route", "prefill.pass", "hop",
                     "hop", "queue"]
    parents = [s.parent for s in tr.spans]
    assert parents == [None, 0, 1, 1, 3, 0, None]
    assert [s.seconds for s in tr.spans] == [11, 7, 1, 3, 1, 1, 2]
    assert tr.spans[3].detail == {"kind": "cold"}
    assert tr.spans[5].rid == "a"
    # schedule less its prompt pass (the hop inside the pass counts once)
    assert exclusive_seconds(tr.spans, "schedule",
                             {"prefill.pass", "hop"}) == {1: 4}
    assert exclusive_seconds(tr.spans, "tick", {"hop"}) == {0: 9}


def test_span_opens_a_profiler_annotation(monkeypatch):
    seen = []

    class Ann:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            seen.append("/")

    tr = Tracer(enabled=True)
    monkeypatch.setattr(tr, "_annotation", Ann)
    with tr.span("tick"):
        with tr.span("schedule"):
            pass
    assert seen == ["repro:tick", "repro:schedule", "/", "/"]
    assert isinstance(tr.spans[0], Span)


# ---------------------------------------------------------------- cluster --

@pytest.fixture(scope="module")
def served():
    """The same requests served with the tracer on and off: more requests
    than slots, prompts that repeat (prefix-cache resumes)."""
    cfg = get_reduced("phi4-mini-3.8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.bfloat16)
    out = {}
    for on in (False, True):
        cluster = DisaggregatedCluster(model, params, num_decode=2,
                                       slots_per_worker=1, max_len=64,
                                       max_prefill_batch=2)
        if on:
            cluster.tracer.enable()
        reqs = [ServeRequest(f"r{i}", [t % cfg.vocab_size for t in
                                       template_tokens(i % 2, 32)],
                             max_new_tokens=3) for i in range(5)]
        for r in reqs:
            cluster.submit(r)
        ticks = 0
        while cluster.pending or cluster.running:
            cluster.step()
            ticks += 1
        out[on] = (cluster, reqs, ticks)
    return out


def test_every_span_is_recorded_with_its_parent_and_rid(served):
    cluster, reqs, ticks = served[True]
    spans = cluster.tracer.spans
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert set(by) == {"submit", "tick", "schedule", "route", "route.select",
                       "route.export", "queue", "prefill.pass", "hop",
                       "decode_step", "decode.dispatch", "decode.wait",
                       "complete", "telemetry"}

    def parent(s):
        return spans[s.parent].name if s.parent is not None else None

    rids = {r.request_id for r in reqs}
    expect = {"submit": None, "tick": None, "schedule": "tick",
              "route": "schedule", "route.select": "route",
              "route.export": "route", "queue": None,
              "prefill.pass": "schedule", "hop": "schedule",
              "decode_step": "tick", "decode.dispatch": "decode_step",
              "decode.wait": "decode_step", "complete": "tick",
              "telemetry": "tick"}
    for name, group in by.items():
        assert {parent(s) for s in group} == {expect[name]}, name
        assert all(s.end >= s.start for s in group)
    assert len(by["tick"]) == ticks
    for name in ("submit", "route", "queue", "hop"):
        assert {s.rid for s in by[name]} <= rids and \
            {s.rid for s in by[name] if s.rid} == rids, name
    assert len(by["submit"]) == len(by["queue"]) == len(by["hop"]) == 5
    assert [s.detail for s in by["submit"]] == [32] * 5
    assert {s.detail for s in by["queue"]} <= {0, 1}
    for s in by["queue"]:
        r = next(r for r in reqs if r.request_id == s.rid)
        assert s.end - s.start == pytest.approx(r.placed_t - r.submit_t)
    kinds = {s.detail["kind"] for s in by["prefill.pass"]}
    assert kinds == {"cold", "resume"}
    assert sorted(rid for s in by["prefill.pass"]
                  for rid in s.detail["rids"] if rid) == sorted(rids)
    for s in by["decode_step"]:
        assert s.detail["worker"] in (0, 1) and s.detail["lengths"]
        assert s.detail["kv_write_in_place"] is True
    assert sum(s.detail for s in by["complete"]) == 5
    assert all(isinstance(s.detail, int) for s in by["schedule"])


def test_counters_count_retries_of_blocked_requests(served):
    for on in (False, True):
        cluster, reqs, ticks = served[on]
        m = cluster.metrics
        calls = m.counter("route_calls_total").value
        placed = m.counter("placements_total").value
        assert placed == m.counter("hops_total").value == 5
        assert calls > placed            # 5 requests, 2 slots: retries
        assert calls == sum(r.route_calls for r in reqs)
        assert m.counter("ticks_total").value == ticks
        assert all(r.placed_t >= r.submit_t > 0.0 for r in reqs)
        text = m.export_text()
        for name in ("route_calls_total", "placements_total", "ticks_total",
                     "hops_total"):
            assert f"\n{name} " in text


def test_tracing_does_not_change_the_served_tokens(served):
    off, on = served[False][1], served[True][1]
    assert [r.output for r in on] == [r.output for r in off]
    assert all(len(r.output) == 4 for r in on)
    assert served[False][0].tracer.spans == []
