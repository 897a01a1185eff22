"""The one traffic generator: it reads a mix's parameters
(``traffic/<mix>.json``) and a cell's load (``cells/<cell>.json``) and
returns a schedule that is a pure function of the seed.

Two loops:

* ``open``: multi-turn chat sessions start as a Poisson process.  Turn 1
  opens with one of a few shared templates drawn Zipf-heavy (as the
  lmsys-like generator in ``repro.traces.generators`` draws them) and a
  new user input; each later turn resends the previous turn's whole
  prompt, the previous reply and a new input, so sessions extend their
  own prefixes.  Replies are seeded tokens of the turn's ``max_new``
  length standing in for the generated ones, so the schedule never
  depends on what the system generates.  Every request is due at a
  fixed time.
* ``closed``: each client sends its next request when its last one
  completes; prompts are unique random tokens that share nothing.

The sizes and the times are drawn once, from the mix's ``shape_seed``:
when each session starts, how many turns it has, its template, every
input and reply length and every turn gap.  The run's seed draws only
the token ids (and, in ``run.py``, the weights).  So every seed offers
the same work at the same times, and two seeds differ by what the
tokens say, not by how the load falls: a queue's tail then moves with
the system, not with the draw.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List

import numpy as np


@dataclass
class Turn:
    t: float                       # due time, seconds from the start
    prompt: List[int]
    max_new: int
    session: str
    last: bool                     # last turn of its session
    template: int = -1             # template id of the session (open loop)


@dataclass
class Schedule:
    loop: str                      # "open" | "closed"
    warm_s: float
    turns: List[Turn] = field(default_factory=list)        # open loop
    clients: List[List[Turn]] = field(default_factory=list)   # closed


def _normal(rng, spec: dict, n: int) -> np.ndarray:
    x = np.rint(rng.normal(spec["mean"], spec["std"], size=n)).astype(int)
    x = np.maximum(x, spec.get("min", 1))
    if "max" in spec:
        x = np.minimum(x, spec["max"])
    return x


def _tokens(rng, vocab: int, n: int) -> List[int]:
    return rng.integers(1, vocab, size=int(n)).tolist()


def chat_schedule(mix: dict, rate: float, seed: int, horizon_s: float,
                  vocab: int, max_len: int) -> Schedule:
    """Open-loop sessions over ``horizon_s`` seconds at ``rate`` session
    starts per second."""
    s = mix["sessions"]
    shape = np.random.default_rng(mix["shape_seed"])
    n = max(1, int(math.ceil(rate * horizon_s)))
    gaps = shape.exponential(1.0 / rate, size=n)
    lo, hi = s["turns"]
    n_turns = shape.integers(lo, hi + 1, size=n)
    tpl = np.minimum(s["templates"] - 1,
                     shape.zipf(s["template_zipf"], size=n) - 1)
    inputs = _normal(shape, s["input_tokens"], n * hi).reshape(n, hi)
    replies = _normal(shape, s["reply_tokens"], n * hi).reshape(n, hi)
    tg = s["turn_gap_s"]
    turn_gaps = (tg["fixed"]
                 + shape.exponential(tg["exp_mean"], size=(n, hi)))

    rng = np.random.default_rng(seed)
    templates = [_tokens(rng, vocab, s["template_tokens"])
                 for _ in range(s["templates"])]
    starts = np.cumsum(gaps) - gaps[0]
    turns: List[Turn] = []
    for k in range(n):
        t = float(starts[k])
        prompt = list(templates[tpl[k]])
        reply: List[int] = []
        planned = []
        for j in range(n_turns[k]):
            prompt = prompt + reply + _tokens(rng, vocab, inputs[k, j])
            mx = int(replies[k, j])
            if len(prompt) + mx + 1 > max_len:
                break                  # the session ends before this turn
            planned.append(Turn(t, prompt, mx, f"s{k}", False, int(tpl[k])))
            reply = _tokens(rng, vocab, mx)
            t += float(turn_gaps[k, j])
        if planned:
            planned[-1].last = True
        turns.extend(planned)
    turns.sort(key=lambda x: x.t)
    return Schedule("open", mix["warm_s"], turns=turns)


def unique_schedule(mix: dict, n_clients: int, seed: int, vocab: int,
                    max_len: int) -> Schedule:
    """Closed loop: ``n_clients`` clients, each with its own queue of
    unique prompts."""
    r = mix["requests"]
    per = mix["per_client"]
    shape = np.random.default_rng(mix["shape_seed"])
    total = n_clients * per
    plen = _normal(shape, r["prompt_tokens"], total)
    mnew = _normal(shape, r["reply_tokens"], total)
    mnew = np.minimum(mnew, max_len - 1 - plen)
    rng = np.random.default_rng(seed)
    clients = []
    for c in range(n_clients):
        q = []
        for j in range(per):
            k = c * per + j
            q.append(Turn(0.0, _tokens(rng, vocab, plen[k]), int(mnew[k]),
                          f"c{c}", True))
        clients.append(q)
    return Schedule("closed", mix["warm_s"], clients=clients)


def build(mix: dict, cell: dict, seed: int, seconds: float, vocab: int,
          max_len: int, n_slots: int) -> Schedule:
    if mix["loop"] == "open":
        horizon = mix["warm_s"] + seconds + mix["tail_s"]
        return chat_schedule(mix, cell["session_rate_per_s"], seed,
                             horizon, vocab, max_len)
    if mix["loop"] == "closed":
        n = n_slots if cell.get("clients", "slots") == "slots" \
            else int(cell["clients"])
        return unique_schedule(mix, n, seed, vocab, max_len)
    raise ValueError(f"unknown loop {mix['loop']!r}")
