"""The per-layer readers of what the program records itself (request
stamps and counters, the named hop program), on synthetic inputs."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from benchtest_common import (ROOT, TINY_CLOSED, harness, run_tiny,
                              tiny_config)

from bench import tracefile


def _track(submit, serve):
    return SimpleNamespace(submit=submit, serve=serve)


def _serve(submit_t, placed_t=0.0, route_calls=0):
    return SimpleNamespace(submit_t=submit_t, placed_t=placed_t,
                           route_calls=route_calls)


# benchmark clock = cluster clock + 100 s
TRACKS = [
    _track(105.0, _serve(5.0, 5.002, 3)),      # placed in the window
    _track(108.0, _serve(8.0, 8.010, 1)),      # placed in the window
    _track(119.9, _serve(19.9, 20.5, 40)),     # placed after the window
    _track(104.0, _serve(4.0, 0.0, 7)),        # never placed
    _track(101.0, _serve(1.0, 1.5, 9)),        # placed before the window
]
WINDOW = (105.0, 120.0)


def test_queue_wait_is_the_mean_of_requests_placed_in_the_window():
    got = harness.reader("queue_wait_ms").read(
        {"window": WINDOW, "tracks": TRACKS}, None)
    assert got == pytest.approx(1e3 * (0.002 + 0.010) / 2)


def test_route_calls_per_placement_reads_the_requests_placed_in_the_window():
    for name in ("route_calls_per_placement",
                 "route_calls_per_placement.closed"):
        got = harness.reader(name).read(
            {"window": WINDOW, "tracks": TRACKS}, name.partition(".")[2]
            or None)
        assert got == pytest.approx((3 + 1) / 2)


@pytest.mark.parametrize("name", ["queue_wait_ms",
                                  "route_calls_per_placement.closed"])
def test_request_readers_without_stamps_give_no_value(name):
    """A program that stamps no placement (or a window with none) gives no
    value, and does not raise."""
    bare = [_track(106.0, SimpleNamespace(submit_t=6.0)),
            _track(107.0, None)]
    for tracks in (bare, [], TRACKS[2:]):
        assert harness.reader(name).read(
            {"window": WINDOW, "tracks": tracks}, None) is None


class Spans:
    def __init__(self, admits):
        self.admits = admits

    def within(self, name, a, b):
        assert name == "admit"
        return [s for s in self.admits if a <= s[0] < b]


def _hop_trace():
    ops = [
        # the hop's program: a fusion enclosing a copy, counted once
        {"chip": 0, "module": "jit_kv_hop(7)", "name": "fusion.1",
         "start_ns": 2000.0, "dur_ns": 400.0},
        {"chip": 0, "module": "jit_kv_hop(7)", "name": "copy.2",
         "start_ns": 2100.0, "dur_ns": 100.0},
        {"chip": 0, "module": "jit_kv_hop(7)", "name": "fusion.1",
         "start_ns": 6000.0, "dur_ns": 200.0},
        # half of it past the window's end
        {"chip": 0, "module": "jit_kv_hop(7)", "name": "fusion.1",
         "start_ns": 10900.0, "dur_ns": 200.0},
        # another program whose name starts alike
        {"chip": 0, "module": "jit_kv_hop_paged(8)", "name": "fusion.9",
         "start_ns": 3000.0, "dur_ns": 900.0},
        {"chip": 0, "module": "jit__unknown(1)", "name": "fusion.3",
         "start_ns": 4000.0, "dur_ns": 900.0},
    ]
    host = [{"name": tracefile.WINDOW_SPAN, "start_ns": 1000.0,
             "dur_ns": 10000.0}]
    return {"ops": ops, "host": host}


def test_hop_ms_is_device_time_of_the_hop_program_per_hop():
    ctx = {"trace": _hop_trace(), "traced": (1.0, 5.0),
           "spans": Spans([(0.5, 0.6, None), (1.5, 1.6, None),
                           (2.5, 2.6, None), (3.0, 3.1, None),
                           (5.5, 5.6, None)])}
    got = harness.reader("hop_ms").read(ctx, None)
    assert got == pytest.approx((400 + 200 + 100) / 1e6 / 3)


def test_hop_ms_without_its_program_or_hops_gives_no_value():
    tr = _hop_trace()
    spans = Spans([(1.5, 1.6, None)])
    base = {"trace": tr, "traced": (1.0, 5.0), "spans": spans}
    read = harness.reader("hop_ms").read
    unnamed = dict(tr, ops=[dict(e, module="jit__insert_cache(3)")
                            for e in tr["ops"]])
    assert read(dict(base, trace=unnamed), None) is None
    assert read(dict(base, spans=Spans([])), None) is None
    assert read(dict(base, trace=None), None) is None
    assert read(dict(base, traced=None), None) is None
    assert read(dict(base, trace=dict(tr, host=[])), None) is None


def test_recorded_trace_has_no_hop_program():
    """The small recorded trace predates the named hop: no value, and its
    breakdown is read as before."""
    with open(ROOT / "bench" / "fixtures" / "trace_small.json") as f:
        tr = json.load(f)
    ctx = {"trace": tr, "traced": (0.0, 1e9),
           "spans": Spans([(1.0, 1.1, None)])}
    assert harness.reader("hop_ms").read(ctx, None) is None
    bd = tracefile.breakdown(tr)
    assert [g[0] for g in bd["idle_gaps"]] == ["decode step",
                                               "generator wait", "other"]


@pytest.mark.parametrize("cell,config,metric", [
    ("phi4mini-sessions-8c", "phi4-mini-3.8b", "queue_wait_ms"),
    ("minitron-sessions-closed", "minitron-4b",
     "route_calls_per_placement.closed"),
])
def test_traced_run_reads_the_program_stamps(cell, config, metric):
    """Two clients per slot: requests wait, and are routed more than once
    before they are placed."""
    mix = dict(TINY_CLOSED, clients_per_slot=2)
    res = run_tiny(cell, mix, tiny_config(config), trace=True, seconds=3.0)
    assert res["correct"]
    value = res["metrics"][metric]["value"]
    assert value > (1.0 if metric.startswith("route") else 0.0)
    # no TPU plane in a CPU trace: no hop program to read
    assert "hop_ms" not in res["metrics"]
