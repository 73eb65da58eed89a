"""One traffic generator for every mix file under ``bench/traffic/``.

A mix file holds parameters only.  ``kind`` picks the prompt structure:

* ``sessions``: a few system prompts with Zipf popularity; each session
  has up to ``turns`` turns, and turn t's prompt is turn t-1's prompt, a
  seeded stand-in for the previous answer and a new user message (block
  reuse in the manner of Mooncake, arXiv:2407.00079);
* ``unshared``: every prompt is distinct seeded tokens, lengths lognormal.

``loop`` is ``open`` (arrivals scheduled in advance at ``rate_per_s``,
whether or not earlier requests finished) or ``closed`` (``clients_per_slot``
clients per decode slot, each sending its next turn as soon as its last
answer completes).

Every draw of a size or a gap is a stratified quantile of its distribution,
permuted by the seed: each seed gets the same set of sizes and arrivals in
another order, so seeds change the order of the work and not its amount.
A closed loop consumes its sessions and output lengths in order, and a
window holds only the first hundred or so, so there the set is stratified
per block (``SESSION_BLOCK`` sessions, ``OUTPUT_BLOCK`` output lengths)
and each block is permuted on its own: every stretch of a few blocks holds
the same work whatever the seed.
A mix may fix that order with ``plan_seed``: sizes, gaps and their order
then come from it and not from the run's seed, which still draws every
token (and the weights).  A closed loop needs it where a tail is judged:
there the order decides which prompts meet in one tick's prompt passes,
and so the tail itself.
All lengths lie on a ``grid``-token grid, which bounds the prompt shapes
the server has to compile.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Optional, Sequence

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
SESSION_BLOCK = 12   # closed loop: sessions that hold the whole stratified mix
OUTPUT_BLOCK = 16    # closed loop: output lengths that hold their distribution


@dataclass
class Req:
    rid: str
    tokens: List[int]
    out_len: int              # output tokens, the first (from prefill) included
    due: Optional[float]      # seconds after the start of traffic; None = closed
    session: int = 0
    turn: int = 1
    client: int = -1


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    with open(directory / f"{name}.json") as f:
        return json.load(f)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per stream of one seed (any non-negative int)."""
    return np.random.default_rng([int(seed), int(stream)])


def plan_rng(mix: dict, seed: int) -> np.random.Generator:
    """The generator that orders a mix's sizes and gaps: the mix's
    ``plan_seed`` where it has one, else the run's seed."""
    return rng_for(mix.get("plan_seed", seed), 3)


def _exp_quantiles(n: int, mean: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-u)


def _lognormal_quantiles(n: int, spec: dict, grid: int = 1) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal with ``median`` and
    ``sigma``, clipped to [``min``, ``max``] and rounded to ``grid``."""
    nd = NormalDist()
    u = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(x) for x in u])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    v = np.clip(np.round(v / grid) * grid, spec["min"], spec["max"])
    return v.astype(np.int64)


def _stratified_choice(n: int, weights: Sequence[float]) -> np.ndarray:
    """Index ``i`` in the share ``weights[i]`` of ``n`` draws, by quantile."""
    cdf = np.cumsum(np.asarray(weights, float) / float(np.sum(weights)))
    u = (np.arange(n) + 0.5) / n
    return np.searchsorted(cdf, u, side="right").clip(0, len(weights) - 1)


def zipf_weights(k: int, s: float) -> List[float]:
    return [1.0 / (i + 1) ** s for i in range(k)]


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, size=n).tolist()


def clients(mix: dict, deployment: dict) -> int:
    """Closed loop: client count from the deployment's decode slots."""
    slots = deployment["num_decode"] * deployment["slots_per_worker"]
    return int(mix["clients_per_slot"] * slots)


# ----------------------------------------------------------- sessions ------

def _session_turn_lengths(mix: dict, sys_len: int) -> List[int]:
    step = mix["answer_tokens"] + mix["user_tokens"]
    out = []
    for t in range(max(mix["turns"])):
        n = sys_len + mix["user_tokens"] + t * step
        if n > mix["max_prompt_tokens"]:
            break
        out.append(n)
    return out


class _Sessions:
    """Seeded session material: system prompts and per-session turns."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        r = rng_for(seed, 1)
        self.system = [_tokens(r, n, vocab) for n in mix["system_prompt_tokens"]]
        self.tok_rng = rng_for(seed, 2)

    def prompts(self, sys_idx: int, n_turns: int) -> List[List[int]]:
        mix = self.mix
        prompt = list(self.system[sys_idx])
        lengths = _session_turn_lengths(mix, len(prompt))[:n_turns]
        out = []
        for t in range(len(lengths)):
            if t:
                prompt = prompt + _tokens(self.tok_rng, mix["answer_tokens"],
                                          self.vocab)
            prompt = prompt + _tokens(self.tok_rng, mix["user_tokens"],
                                      self.vocab)
            out.append(prompt)
        return out


def _session_plan(mix: dict, n: int, perm: np.random.Generator):
    """(system prompt index, turn count) of ``n`` sessions, stratified."""
    k = len(mix["system_prompt_tokens"])
    sys_idx = _stratified_choice(n, zipf_weights(k, mix["zipf_s"]))
    turns = np.asarray(mix["turns"])[
        _stratified_choice(n, [1.0] * len(mix["turns"]))]
    return perm.permutation(sys_idx), perm.permutation(turns)


def _open_sessions(mix: dict, seed: int, vocab: int, horizon: float,
                   rate: float) -> List[Req]:
    perm = plan_rng(mix, seed)
    mean_turns = float(np.mean(mix["turns"]))
    s_rate = rate / mean_turns
    n = int(math.ceil(s_rate * horizon * 1.2)) + 4
    starts = np.cumsum(perm.permutation(_exp_quantiles(n, 1.0 / s_rate)))
    sys_idx, turns = _session_plan(mix, n, perm)
    gaps = perm.permutation(_exp_quantiles(int(turns.sum()),
                                           mix["turn_gap_mean_s"]))
    outs = perm.permutation(_lognormal_quantiles(int(turns.sum()),
                                                 mix["output"]))
    mat = _Sessions(mix, seed, vocab)
    reqs, g = [], 0
    for s in range(n):
        t_due = float(starts[s])
        for t, prompt in enumerate(mat.prompts(int(sys_idx[s]), int(turns[s]))):
            if t:
                t_due += float(gaps[g])
            if t_due < horizon:
                reqs.append(Req(f"s{s}t{t + 1}", prompt, int(outs[g]), t_due,
                                session=s, turn=t + 1))
            g += 1
    reqs.sort(key=lambda r: r.due)
    return reqs


def _blocks(items: np.ndarray, n: int, perm: np.random.Generator) -> np.ndarray:
    """At least ``n`` values: copies of ``items``, each in its own seeded
    order."""
    reps = -(-n // len(items))
    return np.concatenate([perm.permutation(items) for _ in range(reps)])


class ClosedSessions:
    """Closed loop: each of ``n_clients`` clients runs sessions back to
    back and sends its next turn when its last answer completes.  Sessions
    are taken in one global order, block by block (``SESSION_BLOCK``)."""

    def __init__(self, mix: dict, seed: int, vocab: int, n_clients: int,
                 per_client: int = 64):
        perm = plan_rng(mix, seed)
        self.mat = _Sessions(mix, seed, vocab)
        self.n_clients = n_clients
        k = len(mix["system_prompt_tokens"])
        # one block: system prompts stratified by popularity, turn counts
        # dealt round in that order, so each prompt gets its share of each
        sys_b = _stratified_choice(SESSION_BLOCK, zipf_weights(k, mix["zipf_s"]))
        turns_b = np.asarray(mix["turns"])[np.arange(SESSION_BLOCK)
                                           % len(mix["turns"])]
        order = _blocks(np.arange(SESSION_BLOCK), per_client * n_clients, perm)
        self.sys_idx, self.turns = sys_b[order], turns_b[order]
        self.outs = _blocks(_lognormal_quantiles(OUTPUT_BLOCK, mix["output"]),
                            int(self.turns.sum()), perm)
        self._next_session = 0
        self._queue: Dict[int, List[Req]] = {c: [] for c in range(n_clients)}
        self._o = 0

    def next_request(self, client: int) -> Req:
        q = self._queue[client]
        if not q:
            s = self._next_session
            self._next_session += 1
            for t, prompt in enumerate(self.mat.prompts(
                    int(self.sys_idx[s % len(self.sys_idx)]),
                    int(self.turns[s % len(self.turns)]))):
                q.append(Req(f"c{client}s{s}t{t + 1}", prompt,
                             int(self.outs[self._o % len(self.outs)]), None,
                             session=s, turn=t + 1, client=client))
                self._o += 1
        return q.pop(0)


# ----------------------------------------------------------- unshared ------

def _open_unshared(mix: dict, seed: int, vocab: int, horizon: float,
                   rate: float) -> List[Req]:
    perm = plan_rng(mix, seed)
    n = int(math.ceil(rate * horizon * 1.2)) + 4
    dues = np.cumsum(perm.permutation(_exp_quantiles(n, 1.0 / rate)))
    lens = perm.permutation(_lognormal_quantiles(n, mix["prompt"],
                                                 grid=mix["grid"]))
    outs = perm.permutation(_lognormal_quantiles(n, mix["output"]))
    tok = rng_for(seed, 2)
    return [Req(f"u{i}", _tokens(tok, int(lens[i]), vocab), int(outs[i]),
                float(dues[i]))
            for i in range(n) if dues[i] < horizon]


def open_schedule(mix: dict, seed: int, vocab: int,
                  horizon: float) -> List[Req]:
    """Every request of an open-loop mix due before ``horizon`` seconds,
    in due order."""
    rate = float(mix["rate_per_s"])
    if mix["kind"] == "sessions":
        return _open_sessions(mix, seed, vocab, horizon, rate)
    if mix["kind"] == "unshared":
        return _open_unshared(mix, seed, vocab, horizon, rate)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


# -------------------------------------------------------------- shapes -----

def prompt_shapes(mix: dict) -> Dict[str, List[int]]:
    """Prompt lengths a mix can send, and the prefix-cache resume suffix
    lengths its shared prefixes can produce (prefix caches match whole
    16-token blocks; every length here is a multiple of 16)."""
    if mix["kind"] == "unshared":
        p = mix["prompt"]
        lo = int(math.ceil(p["min"] / mix["grid"]) * mix["grid"])
        return {"prompt_lengths": list(range(lo, int(p["max"]) + 1,
                                             mix["grid"])),
                "resume_suffixes": []}
    lengths, suffixes = set(), set()
    step = mix["answer_tokens"] + mix["user_tokens"]
    for s in mix["system_prompt_tokens"]:
        ls = _session_turn_lengths(mix, s)
        lengths.update(ls)
        for t, n in enumerate(ls):
            suffixes.add(n - s)                  # another session, same system
            for dt in range(1, t + 1):
                suffixes.add(dt * step)          # an earlier turn of this one
    # a turn placed after a later turn of its session (held back by a full
    # decoder) finds its whole prompt cached and recomputes one token
    suffixes.add(1)
    return {"prompt_lengths": sorted(lengths),
            "resume_suffixes": sorted(suffixes)}
