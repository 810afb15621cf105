"""The one traffic generator: users in sessions, open or closed loop.

A traffic mix is a JSON file of parameters (``bench/traffic/<mix>.json``)
whose ``kind`` names this module.  It describes:

* ``loop``: ``"open"`` — sessions start as a Poisson process at
  ``request_rate_per_s`` / mean turns, and every turn is due at a fixed
  time, whatever the server does; or ``"closed"`` — ``clients`` users,
  each sending its next request ``think_s`` after its previous one
  finished.
* ``system_prompts``: ``count`` shared prompts of ``min``..``max`` tokens,
  a multiple of ``align`` (default 1) long, chosen by Zipf(``zipf``)
  popularity (``count`` 0: no shared prefix).
* ``turns``, ``think_s``, ``user``, ``reply``, ``output``: distributions
  (see :func:`draw`).  Turn k's prompt is the system prompt, then for
  every earlier turn its user text and a seeded reply, then turn k's
  user text.  A session ends before a prompt would pass ``prompt_cap``.
* ``lead_in_s``: traffic that starts before the measured window, so the
  window opens on a loaded server.  ``drain_s``: how long an open-loop
  run waits after the window for requests still in flight.  ``trace_s``:
  how much of the window's end a ``--trace 1`` run profiles.
* ``structure_seed``: the sizes, turn counts, times and origins are drawn
  from it and not from the run's seed, so every seed offers the same
  work at the same times; the run's seed draws every token.

Every session's first user token differs from every other session's, in
every mix: two sessions agree on no token past their shared system
prompt.  The program's prefix cache forks a cached page copy-on-write
when a prompt agrees with it on even one token inside the page, and that
fork serves wrong tokens today (PERF.md, Open questions, item 1); a
chance agreement on one token would fail a run at random.  Real prompts
agree more (template tokens after the system prompt), so this goes once
the fork is fixed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    """One request the plan will send."""

    rid: int
    prompt: np.ndarray          # (1, S) int32
    max_new_tokens: int
    origin: int
    session: int
    turn: int
    due_s: Optional[float] = None   # open loop: window-relative due time
    client: Optional[int] = None    # closed loop: the client that sends it
    think_s: float = 0.0            # closed loop: wait after the previous


@dataclasses.dataclass
class Plan:
    loop: str
    lead_in_s: float
    drain_s: float
    items: List[Item]           # open: by due time; closed: per client order
    clients: int = 0

    def client_queues(self) -> List[List[Item]]:
        qs: List[List[Item]] = [[] for _ in range(self.clients)]
        for it in self.items:
            qs[it.client].append(it)
        return qs


def draw(rng: np.random.Generator, spec: dict, n: int = None):
    """Samples of one distribution spec.

    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` rounds and
    clips to [min, max]; ``"geometric"`` has ``mean`` and is clipped the
    same way; ``"exponential"`` has ``mean``; ``"fixed"`` has ``value``.
    """
    kind = spec["dist"]
    if kind == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    elif kind == "geometric":
        x = rng.geometric(1.0 / spec["mean"], n)
        x = np.clip(x, spec.get("min", 1), spec.get("max", np.inf))
        x = x.astype(np.int64)
    elif kind == "exponential":
        x = rng.exponential(spec["mean"], n)
    elif kind == "fixed":
        x = np.full(n if n is not None else (), spec["value"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return x


def mean_of(spec: dict) -> float:
    """Mean of a count distribution after its clipping (session rate =
    request rate / mean turns)."""
    kind = spec["dist"]
    if kind == "fixed":
        return float(spec["value"])
    if kind == "geometric":
        p, lo, hi = 1.0 / spec["mean"], spec.get("min", 1), spec.get("max")
        if hi is None:
            return spec["mean"]
        # E[clip(G, lo, hi)] with P(G = k) = (1-p)^(k-1) p
        ks = np.arange(1, hi)
        pk = (1 - p) ** (ks - 1) * p
        return float((np.maximum(ks, lo) * pk).sum() + hi * (1 - pk.sum()))
    raise ValueError(f"no mean for {kind!r}")


@dataclasses.dataclass
class _Shape:
    """A session's sizes, drawn from the structure seed."""

    system: int                 # index of its system prompt, or -1
    users: List[int]
    replies: List[int]
    outputs: List[int]
    thinks: List[float]


def _shapes(mix: dict, rng: np.random.Generator, n: int,
            sys_lens: List[int]) -> List[_Shape]:
    sp = mix.get("system_prompts", {"count": 0})
    if sp["count"]:
        w = 1.0 / np.arange(1, sp["count"] + 1) ** sp.get("zipf", 1.0)
        systems = rng.choice(sp["count"], n, p=w / w.sum())
    else:
        systems = np.full(n, -1)
    shapes = []
    cap = mix["prompt_cap"]
    for s in range(n):
        turns = int(draw(rng, mix["turns"]))
        users = draw(rng, mix["user"], turns).tolist()
        replies = (draw(rng, mix["reply"], turns).tolist()
                   if mix.get("reply") else [0] * turns)
        outputs = draw(rng, mix["output"], turns).tolist()
        thinks = np.asarray(draw(rng, mix["think_s"], turns), float).tolist()
        sys_len = sys_lens[systems[s]] if systems[s] >= 0 else 0
        total, keep = sys_len, 0
        for k in range(turns):
            total += users[k]
            if total > cap:
                break
            keep = k + 1
            total += replies[k]
        keep = max(keep, 1)
        if sys_len + users[0] > cap:        # the first turn alone is too long
            users[0] = cap - sys_len
        shapes.append(_Shape(int(systems[s]), users[:keep], replies[:keep],
                             outputs[:keep], thinks[:keep]))
    return shapes


def _prompt(shape: _Shape, k: int, texts: dict, systems: List[np.ndarray]
            ) -> np.ndarray:
    parts = [systems[shape.system]] if shape.system >= 0 else []
    for j in range(k):
        parts += [texts["user", j], texts["reply", j]]
    parts.append(texts["user", k])
    return np.concatenate(parts).astype(np.int32)[None]


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             origins: int) -> Plan:
    """The plan of requests for one run: ``seconds`` of window after
    ``mix["lead_in_s"]`` of lead-in.  Deterministic in (mix, seed,
    seconds)."""
    srng = np.random.default_rng(mix["structure_seed"])
    rng = np.random.default_rng(seed)
    sp = mix.get("system_prompts", {"count": 0})
    align = int(sp.get("align", 1))
    sys_lens = ((srng.integers(-(-sp["min"] // align), sp["max"] // align + 1,
                               sp["count"]) * align).tolist()
                if sp["count"] else [])
    systems = [rng.integers(0, vocab, n).astype(np.int32) for n in sys_lens]
    lead = float(mix["lead_in_s"])

    if mix["loop"] == "open":
        rate = mix["request_rate_per_s"] / mean_of(mix["turns"])
        starts, t = [], -lead
        while True:
            t += float(srng.exponential(1.0 / rate))
            if t >= seconds:
                break
            starts.append(t)
        shapes = _shapes(mix, srng, len(starts), sys_lens)
        clients = 0
    elif mix["loop"] == "closed":
        clients = int(mix["clients"])
        per = int(mix["sessions_per_client"])
        shapes = _shapes(mix, srng, clients * per, sys_lens)
        starts = None
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")

    origin_of = srng.integers(0, origins, len(shapes))
    heads = rng.permutation(vocab)[:len(shapes)]
    items: List[Item] = []
    for slot, shape in enumerate(shapes):
        texts = {}
        for j in range(len(shape.users)):
            texts["user", j] = rng.integers(0, vocab, shape.users[j])
            texts["reply", j] = rng.integers(0, vocab, shape.replies[j])
        texts["user", 0][0] = heads[slot]
        origin = int(origin_of[slot])
        due = starts[slot] if starts is not None else None
        for k in range(len(shape.users)):
            it = Item(rid=0, prompt=_prompt(shape, k, texts, systems),
                      max_new_tokens=int(shape.outputs[k]), origin=origin,
                      session=slot, turn=k)
            if starts is not None:
                it.due_s = due
                due += shape.thinks[k]
            else:
                it.client = slot % clients
                it.think_s = shape.thinks[k - 1] if k else 0.0
            items.append(it)
    if starts is not None:
        items = [it for it in items if it.due_s < seconds]
        items.sort(key=lambda it: it.due_s)
    for rid, it in enumerate(items):
        it.rid = rid
    return Plan(loop=mix["loop"], lead_in_s=lead,
                drain_s=float(mix.get("drain_s", 0.0)), items=items,
                clients=clients)
