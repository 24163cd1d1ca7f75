"""The traffic mixes: reproducible from the seed, the same sizes for every
seed, and branch lengths that never depend on timing."""
import sys
from collections import Counter
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from sartbench.spec import BENCH, load_traffic  # noqa: E402
from sartbench.traffic import Traffic  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 987_654_321


@pytest.mark.parametrize("mix", MIXES)
def test_mix_reproduces_for_a_seed(mix):
    spec = load_traffic(mix)
    a = Traffic(spec, BIG_SEED, 151936, 64)
    b = Traffic(spec, BIG_SEED, 151936, 64)
    for i in (0, 1, 31, 32, 77):
        assert a.request(i) == b.request(i)
        assert a.due_s(i) == b.due_s(i)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    spec = load_traffic(mix)
    cycle = spec["cycle"]

    def sizes(seed, block):
        tr = Traffic(spec, seed, 151936, 64)
        reqs = [tr.request(block * cycle + k) for k in range(cycle)]
        return Counter((len(r.prompt), tuple(r.branch_lengths))
                       for r in reqs), [len(r.prompt) for r in reqs]

    s1, order1 = sizes(1, 0)
    s2, order2 = sizes(BIG_SEED, 3)
    assert s1 == s2
    assert order1 != order2
    if spec["loop"] == "open":
        t1, t2 = Traffic(spec, 1, 10, 64), Traffic(spec, BIG_SEED, 10, 64)
        # each block of `cycle` arrivals spans the same time, mean gap 1/rate
        span = cycle / spec["rate_per_s"]
        assert t1.due_s(cycle - 1) == pytest.approx(span)
        assert t2.due_s(2 * cycle - 1) == pytest.approx(2 * span)


@pytest.mark.parametrize("mix", MIXES)
def test_sizes_stay_in_range_and_seeds_differ(mix):
    spec = load_traffic(mix)
    tr = Traffic(spec, 5, 1000, 64)
    other = Traffic(spec, 6, 1000, 64)
    p, b = spec["prompt_tokens"], spec["branch_tokens"]
    for i in range(2 * spec["cycle"]):
        r = tr.request(i)
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert len(r.branch_lengths) == spec["n"]
        assert all(b["min"] <= n <= b["max"] for n in r.branch_lengths)
        assert all(0 <= t < 1000 for t in r.prompt)
    assert tr.request(0).prompt != other.request(0).prompt


def test_branch_lengths_do_not_depend_on_timing():
    """A request's lengths are a function of (seed, index): asking in any
    order, or for any due time first, gives the same answer."""
    spec = load_traffic("reason-poisson")
    forward = Traffic(spec, 9, 100, 64)
    backward = Traffic(spec, 9, 100, 64)
    want = [forward.request(i).branch_lengths for i in range(40)]
    backward.due_s(39)
    got = [backward.request(i).branch_lengths for i in reversed(range(40))]
    assert got[::-1] == want


def test_closed_loop_backlog_follows_slots_and_n():
    spec = load_traffic("reason-backlog")
    assert spec["loop"] == "closed"
    assert Traffic(spec, 1, 100, 64).waiting == 2 * 64 // spec["n"]
    assert Traffic(spec, 1, 100, 32).waiting == 2 * 32 // spec["n"]
    assert Traffic(spec, 1, 100, 64).due_s(3) is None


def test_extends_takes_the_base_and_overrides_keys():
    base, derived = load_traffic("reason-poisson"), load_traffic(
        "reason-backlog")
    assert derived["branch_tokens"] == base["branch_tokens"]
    assert derived["loop"] == "closed" and base["loop"] == "open"
