"""The open-loop generator: deterministic in the seed, the same work for
every seed, lengths inside the mix's clips."""

import collections

import harness

MIX = harness.load_json(harness.BENCH / "traffic" / "chat.json")
GEN = harness.load_module("generators", "open_loop")


def sched(seed, rate=3.0, seconds=40.0, mix=MIX, preroll=0.0):
    return GEN.schedule(mix, rate=rate, seconds=seconds, seed=seed,
                        vocab=49155, preroll=preroll)


def test_deterministic_in_the_seed():
    a, b = sched(2 ** 31 + 17), sched(2 ** 31 + 17)
    assert [(r["due"], r["max_new_tokens"], r["prompt"].tolist())
            for r in a] == [(r["due"], r["max_new_tokens"],
                             r["prompt"].tolist()) for r in b]
    assert [r["due"] for r in sched(5)] != [r["due"] for r in sched(6)]


def test_every_seed_gets_the_same_work():
    a, b = sched(1), sched(2 ** 40 + 3)
    for key in ("max_new_tokens",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["due"] for r in a)[0] == sorted(r["due"] for r in b)[0]
    def gaps(s):        # the last gap runs to the window's close at 40 s
        return sorted([y["due"] - x["due"] for x, y in zip(s, s[1:])]
                      + [40.0 - s[-1]["due"]])
    ga, gb = gaps(a), gaps(b)
    assert max(abs(x - y) for x, y in zip(ga, gb)) < 1e-9


def test_lengths_follow_the_clips_and_the_rate():
    s = sched(9, rate=4.0, seconds=50.0)
    assert len(s) == 200
    assert s[0]["due"] == 0.0 and all(0 <= r["due"] < 50.0 for r in s)
    assert [r["due"] for r in s] == sorted(r["due"] for r in s)
    p = [len(r["prompt"]) for r in s]
    o = [r["max_new_tokens"] for r in s]
    assert min(p) >= 16 and max(p) == 1536 and sorted(p)[100] in (255, 256,
                                                                  257)
    assert min(o) >= 16 and max(o) <= 512
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 2048 for r in s)


def test_bursts_and_sessions():
    mix = dict(MIX, burst=4, sessions={"count": 2, "prefix_len": 8})
    s = sched(3, rate=4.0, seconds=10.0, mix=mix)
    groups = collections.Counter(r["due"] for r in s)
    assert set(groups.values()) == {4}
    prefixes = {tuple(r["prompt"][:8]) for r in s}
    assert len(prefixes) == 2


def test_blocks_are_balanced():
    s = sched(11, rate=1.6, seconds=51.0)
    assert MIX["block"] == 8 and len(s) == 82
    outs = [r["max_new_tokens"] for r in s]
    top = sorted(outs)[-len(outs) // 8:]        # the longest eighth
    for b in range(0, len(outs) - 7, 8):         # one per block, ties aside
        assert sum(o >= min(top) for o in outs[b:b + 8]) <= 2
    due = [r["due"] for r in s]
    span = 51.0 * 8 / 82                         # each block's share of time
    for b in range(8, len(due) - 8, 8):
        assert abs(due[b] - due[b - 8] - span) < 0.5 * span


def test_preroll_comes_first_and_leaves_the_window_as_it_was():
    s = sched(13, rate=1.6, seconds=51.0, preroll=20.0)
    pre = [r for r in s if r["due"] < 0]
    win = [r for r in s if r["due"] >= 0]
    assert len(pre) == 32 and len(win) == 82 and pre[0]["due"] == -20.0
    assert [r["uid"] for r in s] == list(range(len(s)))
    alone = sched(13, rate=1.6, seconds=51.0)
    assert sorted(r["max_new_tokens"] for r in win) == \
        sorted(r["max_new_tokens"] for r in alone)
