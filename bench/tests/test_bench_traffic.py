"""The traffic generator: deterministic per seed, and the same work for
every seed."""
import collections

import numpy as np

from bench.traffic import generate, lengths


def _sizes(tr):
    return (sorted(len(h) for h in tr.histories.values()),
            sorted((len(t.new_ids), t.max_new) for t in tr.turns))


def test_same_seed_same_traffic(tiny_mix):
    a = generate(tiny_mix, 2**31 + 5, 320, 10.0, 256)
    b = generate(tiny_mix, 2**31 + 5, 320, 10.0, 256)
    assert a.histories == b.histories
    assert [(t.at, t.sid, t.new_ids, t.max_new, t.advise_at)
            for t in a.turns] == [(t.at, t.sid, t.new_ids, t.max_new,
                                   t.advise_at) for t in b.turns]


def test_seeds_share_the_schedule_and_draw_their_own_tokens(tiny_mix):
    a = generate(tiny_mix, 1, 320, 10.0, 256)
    b = generate(tiny_mix, 2, 320, 10.0, 256)
    assert [len(h) for h in a.histories.values()] == \
        [len(h) for h in b.histories.values()]
    assert [(t.at, t.sid, len(t.new_ids), t.max_new, t.lead)
            for t in a.turns] == [(t.at, t.sid, len(t.new_ids), t.max_new,
                                   t.lead) for t in b.turns]
    assert a.histories != b.histories
    assert [t.new_ids for t in a.turns] != [t.new_ids for t in b.turns]


def test_history_budget_lengths_and_context_cap(tiny_mix):
    tr = generate(tiny_mix, 7, 320, 10.0, 256)
    hs = tiny_mix["history"]
    assert tr.history_tokens >= hs["over_capacity"] * 320
    for h in tr.histories.values():
        assert hs["min"] <= len(h) <= hs["max"]
    ctx = {s: len(h) + 1 for s, h in tr.histories.items()}
    for t in tr.turns:
        nt = tiny_mix["new_tokens"]
        assert nt["min"] <= len(t.new_ids) <= nt["max"]
        assert t.advise_at <= t.at
        assert 0.0 <= t.at <= 10.0
        assert t.lead <= tiny_mix["advisory"]["max_lead_s"]
        ctx[t.sid] += len(t.new_ids) + t.max_new
        assert ctx[t.sid] <= tiny_mix["max_context"]
    assert all(0 <= i < 256 for t in tr.turns for i in t.new_ids)


def test_lengths_clip_round_and_median():
    rng = np.random.default_rng(0)
    spec = dict(median=384, sigma=0.8, min=64, max=4096)
    x = lengths(rng, spec, 20000)
    assert x.dtype == np.int64 and x.min() == 64 and x.max() == 4096
    assert 374 <= np.median(x) <= 394
    mu = lengths(rng, dict(mu=np.log(384), sigma=0.8, min=64, max=4096),
                 20000)
    assert abs(np.median(mu) - np.median(x)) <= 16


def test_turns_spread_over_sessions(tiny_mix):
    tr = generate(tiny_mix, 3, 320, 10.0, 256)
    per = collections.Counter(t.sid for t in tr.turns)
    assert len(per) > len(tr.histories) // 2
