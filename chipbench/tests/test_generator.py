"""The traffic generator is deterministic by seed, and a seed orders
one fixed set of sizes and gaps rather than drawing new ones."""
import numpy as np
import pytest

import generator

MIXES = ["agents-code", "agents-research", "chat-short"]


def _mix(name):
    return generator.load_mix(name)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    a = generator.make_schedule(_mix(mix), 0.3, 90, 50304, 2**31 + 11)
    b = generator.make_schedule(_mix(mix), 0.3, 90, 50304, 2**31 + 11)
    assert a == b


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_reorder_one_set_of_sessions(mix):
    a = generator.make_schedule(_mix(mix), 0.3, 90, 50304, 7)
    b = generator.make_schedule(_mix(mix), 0.3, 90, 50304, 2**31 + 8)
    assert len(a) == len(b) == 27
    assert [s["turns"][0]["add"] for s in a] != \
        [s["turns"][0]["add"] for s in b]
    # other sessions at other starts ...
    starts = lambda s: [x["start"] for x in s]  # noqa: E731
    assert starts(a) != starts(b)
    assert [x["agent"] for x in a] != [x["agent"] for x in b] or \
        [x["turns"][0]["max_tokens"] for x in a] != \
        [x["turns"][0]["max_tokens"] for x in b]
    # ... drawn from the same sets: agents, gaps between starts
    assert sorted(x["agent"] for x in a) == sorted(x["agent"] for x in b)
    gaps = lambda s: sorted(np.diff(starts(s)).round(9))  # noqa: E731
    for s in (a, b):
        assert starts(s)[0] == 0 and max(starts(s)) < 90
        assert sorted(starts(s)) == starts(s)
    assert len(set(gaps(a)) & set(gaps(b))) >= len(a) - 2


def test_single_turn_seeds_send_the_same_sizes():
    sizes = lambda s: (  # noqa: E731
        sorted(len(x["turns"][0]["add"]) for x in s),
        sorted(x["turns"][0]["max_tokens"] for x in s))
    m = _mix("chat-short")
    a = generator.make_schedule(m, 0.2, 71, 50304, 11)
    b = generator.make_schedule(m, 0.2, 71, 50304, 2**31 + 12)
    assert sizes(a) == sizes(b)
    assert [len(x["turns"][0]["add"]) for x in a] != \
        [len(x["turns"][0]["add"]) for x in b]


def test_a_mix_extends_another():
    base, over = _mix("chat-short"), _mix("chat-over")
    assert over["load"]["sessions_per_s"] > base["load"]["sessions_per_s"]
    assert {k: v for k, v in over.items() if k not in ("about", "load")} \
        == {k: v for k, v in base.items() if k not in ("about", "load")}
    assert over["load"]["ramp_s"] == base["load"]["ramp_s"]


@pytest.mark.parametrize("mix", MIXES)
def test_requests_stay_inside_the_mix_bounds(mix):
    m = _mix(mix)
    least, most, least_new = generator.context_bounds(m)
    for s in generator.make_schedule(m, 0.5, 120, 50304, 3):
        total = 0
        for t in s["turns"]:
            total += len(t["add"]) + t["max_tokens"]
            assert least <= total <= most
            assert all(0 <= x < 50304 for x in t["add"])
            assert m["output"]["min"] <= t["max_tokens"] <= \
                m["output"]["max"]
        assert all(t["tool_s"] >= 0.05 for t in s["turns"][:-1])
        assert s["turns"][-1]["tool_s"] is None
