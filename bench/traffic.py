"""The one traffic generator: multi-turn sessions from a mix's data file.

A mix is ``bench/traffic/<name>.json``.  It states the distributions of
session histories, of each turn's new tokens and answer length, the
arrival process and the advisory lead.  Every size, gap and lead comes
from a fixed stream (the mix's ``size_seed``), and so do their order and
which session takes which turn: every run seed serves the same schedule,
and draws only the token ids (and, in the harness, the weights).  A seed
that reordered the schedule moved a 51 s window's tokens/s by a quarter
between seeds, against a few percent between two runs of one seed.

Lengths are lognormal: ``{"median": m, "sigma": s}`` (or ``"mu"`` for
``log m``), clipped to ``[min, max]`` and rounded up to whole tokens.
Typing-time arithmetic is `repro/traces/sharegpt.py`'s (3.5 tokens/s).
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

import numpy as np

BENCH = Path(__file__).resolve().parent
TYPE_TOK_PER_S = 3.5          # ~70 wpm chat typing (traces/sharegpt.py)


def load_mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lognormal lengths per ``spec``, clipped and rounded up."""
    mu = spec["mu"] if "mu" in spec else np.log(spec["median"])
    x = np.exp(mu + spec["sigma"] * rng.standard_normal(n))
    return np.ceil(np.clip(x, spec["min"], spec["max"])).astype(np.int64)


@dataclass
class Turn:
    at: float                 # scheduled arrival, seconds from window start
    advise_at: float          # when its advisory fires (< 0: in set-up)
    lead: float               # advisory lead, seconds
    sid: str
    new_ids: List[int]
    max_new: int


@dataclass
class Traffic:
    histories: Dict[str, List[int]]        # sid -> token ids built in set-up
    turns: List[Turn] = field(default_factory=list)

    @property
    def history_tokens(self) -> int:
        return sum(len(h) for h in self.histories.values())


def generate(mix: dict, seed: int, capacity_tokens: int, seconds: float,
             vocab: int) -> Traffic:
    """Sessions whose histories sum to ``over_capacity`` times
    ``capacity_tokens``, and the ``rate * seconds`` turns that arrive in a
    window of ``seconds``."""
    sizes = np.random.default_rng(mix["size_seed"])
    rng = np.random.default_rng(int(seed) % (1 << 63))
    hs = mix["history"]
    target = hs["over_capacity"] * capacity_tokens
    pool = lengths(sizes, hs, 4 * int(target // hs["min"]) + 4)
    n_sess = int(np.searchsorted(np.cumsum(pool), target)) + 1
    hist = pool[:n_sess]
    histories = {f"s{i}": [int(t) for t in rng.integers(0, vocab, n)]
                 for i, n in enumerate(hist)}

    # exactly rate * seconds turns arrive in the window: the gaps of a
    # Poisson process given its count (exponentials scaled to the window)
    n_turns = max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))
    gaps = sizes.exponential(1.0, n_turns + 1)
    gaps = gaps * (seconds / gaps.sum())
    news = lengths(sizes, mix["new_tokens"], n_turns)
    outs = lengths(sizes, mix["output_tokens"], n_turns)
    adv = mix["advisory"]
    leads = np.minimum(lengths(sizes, adv["typing_tokens"], n_turns)
                       / adv.get("tokens_per_s", TYPE_TOK_PER_S),
                       adv["max_lead_s"])

    # each turn goes to the next session of a random cycle over the live
    # ones; a session whose context would pass max_context ends instead
    cap = mix.get("max_context")
    ctx = {sid: len(h) + 1 for sid, h in histories.items()}  # + pending
    live = list(histories)
    cycle: List[str] = []
    turns, t = [], 0.0
    for i in range(n_turns):
        t += float(gaps[i])
        sid = None
        while live:
            if not cycle:
                cycle = [live[j] for j in sizes.permutation(len(live))]
            cand = cycle.pop()
            if cand not in live:
                continue
            if cap and ctx[cand] + news[i] + outs[i] > cap:
                live.remove(cand)
                continue
            sid = cand
            break
        if sid is None:
            break
        ctx[sid] += int(news[i] + outs[i])
        turns.append(Turn(at=t, advise_at=t - float(leads[i]),
                          lead=float(leads[i]), sid=sid,
                          new_ids=[int(x) for x in
                                   rng.integers(0, vocab, int(news[i]))],
                          max_new=int(outs[i])))
    return Traffic(histories=histories, turns=turns)
