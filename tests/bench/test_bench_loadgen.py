"""The benchmark's traffic generator (``bench/loadgen.py``) and drivers."""
from __future__ import annotations

import time
from collections import Counter

import pytest

from benchtest_common import FakeServe, TINY_SESSIONS, TINY_UNSHARED, harness

from bench import loadgen

BIG_SEED = 2**33 + 3


def _mixes():
    names = sorted(p.stem for p in loadgen.TRAFFIC_DIR.glob("*.json"))
    assert names, "no traffic mixes"
    return names


@pytest.mark.parametrize("name", _mixes())
def test_mix_deterministic_and_on_grid(name):
    mix = loadgen.load_mix(name)
    shapes = loadgen.prompt_shapes(mix)
    if mix["loop"] == "open":
        a = loadgen.open_schedule(mix, BIG_SEED, 1000, 30.0)
        b = loadgen.open_schedule(mix, BIG_SEED, 1000, 30.0)
        assert [(r.rid, r.tokens, r.out_len, r.due) for r in a] == \
            [(r.rid, r.tokens, r.out_len, r.due) for r in b]
        reqs = a
    else:
        dep = {"num_decode": 2, "slots_per_worker": 8}
        n = loadgen.clients(mix, dep)
        g1 = loadgen.ClosedSessions(mix, BIG_SEED, 1000, n)
        g2 = loadgen.ClosedSessions(mix, BIG_SEED, 1000, n)
        reqs = [g1.next_request(c % n) for c in range(3 * n)]
        assert [r.tokens for r in reqs] == \
            [g2.next_request(c % n).tokens for c in range(3 * n)]
    assert reqs
    for r in reqs:
        assert len(r.tokens) % 128 == 0
        assert len(r.tokens) in shapes["prompt_lengths"]
        assert mix["output"]["min"] <= r.out_len <= mix["output"]["max"]
        assert all(0 <= t < 1000 for t in r.tokens)


@pytest.mark.parametrize("mix", [TINY_SESSIONS, TINY_UNSHARED])
def test_seeds_share_the_set_of_sizes(mix):
    """Seeds permute one stratified set of sizes and gaps."""
    n = 40
    outs = [sorted(loadgen._lognormal_quantiles(n, mix["output"]).tolist())
            for _ in range(2)]
    assert outs[0] == outs[1]
    s1 = loadgen.open_schedule(mix, 5, 512, 20.0)
    s2 = loadgen.open_schedule(mix, 6, 512, 20.0)
    assert s1 and s2
    assert [r.rid for r in s1] != [r.rid for r in s2] or \
        [r.tokens for r in s1] != [r.tokens for r in s2]
    # the counts differ only by the requests near the horizon
    assert abs(len(s1) - len(s2)) <= max(3, len(s1) // 5)


def test_closed_loop_seeds_share_the_work_of_each_block():
    """Any stretch of whole blocks holds the same sessions and output
    lengths for every seed, in another order (a mix without
    ``plan_seed``)."""
    mix = loadgen.load_mix("sessions-closed-2x")
    assert "plan_seed" not in mix
    sys_len = mix["system_prompt_tokens"]
    work = []
    for seed in (5, BIG_SEED):
        g = loadgen.ClosedSessions(mix, seed, 1000, 8)
        blocks = 3 * loadgen.SESSION_BLOCK
        sessions = sorted(zip((sys_len[i] for i in g.sys_idx[:blocks]),
                              g.turns[:blocks].tolist()))
        outs = sorted(g.outs[:3 * loadgen.OUTPUT_BLOCK].tolist())
        work.append((sessions, outs, g.sys_idx[:blocks].tolist()))
    assert work[0][:2] == work[1][:2]
    assert work[0][2] != work[1][2]


def _closed_plan(mix, seed, n=8):
    g = loadgen.ClosedSessions(mix, seed, 1000, n)
    reqs = [g.next_request(c % n) for c in range(6 * n)]
    return ([(r.rid, len(r.tokens), r.out_len, r.session, r.turn)
             for r in reqs], [r.tokens for r in reqs])


def test_plan_seed_fixes_the_order_and_the_seed_draws_the_tokens():
    """With ``plan_seed`` every seed sends the same sizes in the same
    order, closed loop and open; the run's seed still draws the tokens."""
    mix = loadgen.load_mix("sessions-closed-half")
    assert "plan_seed" in mix
    plan_a, tok_a = _closed_plan(mix, 5)
    plan_b, tok_b = _closed_plan(mix, BIG_SEED)
    assert plan_a == plan_b
    assert tok_a != tok_b
    free = {k: v for k, v in mix.items() if k != "plan_seed"}
    assert _closed_plan(free, 5)[0] != _closed_plan(free, BIG_SEED)[0]
    for base in (TINY_SESSIONS, TINY_UNSHARED):
        fixed = dict(base, plan_seed=1)
        s1 = loadgen.open_schedule(fixed, 5, 512, 20.0)
        s2 = loadgen.open_schedule(fixed, BIG_SEED, 512, 20.0)
        assert [(r.rid, len(r.tokens), r.out_len, r.due) for r in s1] == \
            [(r.rid, len(r.tokens), r.out_len, r.due) for r in s2]
        assert [r.tokens for r in s1] != [r.tokens for r in s2]


def test_sessions_reuse_prefixes():
    mix = dict(TINY_SESSIONS)
    reqs = loadgen.open_schedule(mix, 7, 512, 30.0)
    by_session = {}
    for r in reqs:
        by_session.setdefault(r.session, []).append(r)
    multi = [v for v in by_session.values() if len(v) > 1]
    assert multi
    for turns in multi:
        turns.sort(key=lambda r: r.turn)
        for a, b in zip(turns, turns[1:]):
            assert b.tokens[:len(a.tokens)] == a.tokens
            assert b.due > a.due
    systems = Counter(tuple(r.tokens[:128]) for r in reqs if r.turn == 1)
    assert len(systems) <= len(mix["system_prompt_tokens"])


@pytest.mark.parametrize("mix", [TINY_SESSIONS, TINY_UNSHARED])
def test_prompt_shapes_cover_the_program_resumes(mix):
    """Every prompt length and every resume suffix the program's prefix
    cache can produce over a generated stream is in the warm-up set."""
    from repro.configs import get_config
    from repro.configs.base import reduce_config
    from repro.models.model import Model
    from repro.serving.engine import PrefillEngine

    reqs = loadgen.open_schedule(mix, 11, 512, 40.0)
    shapes = loadgen.prompt_shapes(mix)
    assert {len(r.tokens) for r in reqs} <= set(shapes["prompt_lengths"])
    model = Model(reduce_config(get_config("phi4-mini-3.8b")))
    pf = PrefillEngine(model, None, max_len=600, cache_entries=4)
    got = pf.resume_suffixes([r.tokens for r in reqs])
    assert set(got) <= set(shapes["resume_suffixes"])
    if mix["kind"] == "sessions":
        assert got


def test_open_loop_due_times_do_not_depend_on_service():
    mix = dict(TINY_UNSHARED, rate_per_s=20.0, warm_s=0.2)
    sched = loadgen.open_schedule(mix, 3, 512, 1.0)
    runs = []
    for step_s in (0.0005, 0.03):
        t0 = time.monotonic()
        drv = harness.Driver(FakeServe(step_s), lambda t0=t0: time.monotonic() - t0)
        harness.drive_open(drv, sched, 0.2, 1.0, 5.0)
        runs.append(drv)
    due = [[(t.req.rid, t.due) for t in d.tracks] for d in runs]
    assert due[0] == due[1] == [(r.rid, r.due) for r in sched]
    for d in runs:
        assert all(t.submit >= t.due for t in d.tracks)
        assert all(t.done for t in d.tracks)


def test_closed_loop_holds_exactly_c_clients():
    mix = dict(TINY_SESSIONS, loop="closed", clients_per_slot=2)
    dep = {"num_decode": 2, "slots_per_worker": 2}
    n = loadgen.clients(mix, dep)
    assert n == 8
    gen = loadgen.ClosedSessions(mix, 9, 512, n)
    t0 = time.monotonic()
    drv = harness.Driver(FakeServe(0.002, slots=3), lambda: time.monotonic() - t0)
    seen = []
    orig = drv.tick

    def tick():
        if drv.clock() < 0.5:
            seen.append(len(drv.inflight))
        return orig()

    drv.tick = tick
    harness.drive_closed(drv, gen, 0.5, 5.0)
    assert seen and set(seen) == {n}
    assert not drv.inflight
    per_client = Counter(t.req.client for t in drv.tracks)
    assert set(per_client) == set(range(n))
