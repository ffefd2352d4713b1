"""The traffic generator is a pure function of (mix, seed), and every
seed gets the same set of sizes and gaps in another order."""

import collections
import hashlib
import itertools
import math
import statistics
from pathlib import Path

import pytest

from benchmark.lib import traffic

TRAFFIC = Path(__file__).resolve().parents[2] / "benchmark" / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def take(mix, seed, n):
    return list(itertools.islice(traffic.planned(mix, seed), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(TRAFFIC / f"{name}.json")
    a, b = take(mix, 2**31 + 9, 300), take(mix, 2**31 + 9, 300)
    assert a == b
    assert take(mix, 5, 300) != a
    assert [p.prompt for p in take(mix, 5, 4)] != [p.prompt for p in a[:4]]


def head_len(mix):
    fleet = traffic.first_fleet(mix)
    return fleet + traffic.distinct_prompts(mix) if fleet else 0


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_does_the_same_work(name):
    """After a head that is the same for every seed, each seed walks the
    same cycle of sizes from its own entry point."""
    mix = traffic.load_mix(TRAFFIC / f"{name}.json")
    n, h = int(mix["set_size"]), head_len(mix)
    runs = {s: [(p.prompt_tokens, p.output_tokens) for p in take(mix, s, h + 2 * n)] for s in (1, 2, 3_000_000_007)}
    assert runs[1][:h] == runs[2][:h] == runs[3_000_000_007][:h]
    for s, sizes in runs.items():
        assert sorted(sizes[h:h + n]) == sorted(traffic.size_set(mix))
        assert sizes[h:h + n] == sizes[h + n:h + 2 * n]
    assert runs[1][h:h + n] != runs[2][h:h + n]
    assert runs[1][h + 1:h + n] == runs[2][h:h + n - 1]  # the same neighbours, one step on


@pytest.mark.parametrize("name", [m for m in MIXES if "closed" in m])
def test_warm_up_meets_every_prompt_length(name):
    mix = traffic.load_mix(TRAFFIC / f"{name}.json")
    fleet = traffic.first_fleet(mix)
    head = take(mix, 9, traffic.warmup_requests(mix))
    assert {p.prompt_tokens for p in head[fleet:]} == {p for p, _ in traffic.size_set(mix)}
    if "round_to" in mix["prompt_tokens"]:
        # a mix that rounds does so to keep the warm-up's first sights few
        assert traffic.distinct_prompts(mix) <= 12


@pytest.mark.parametrize("name", MIXES)
def test_sizes_stay_inside_the_clip_and_prompts_are_ascii(name):
    mix = traffic.load_mix(TRAFFIC / f"{name}.json")
    for p in take(mix, 7, 600):
        assert mix["prompt_tokens"]["min"] <= p.prompt_tokens <= mix["prompt_tokens"]["max"]
        assert mix["output_tokens"]["min"] <= p.output_tokens <= mix["output_tokens"]["max"]
        ids = traffic.token_ids(p.prompt)
        assert len(ids) == p.prompt_tokens and ids[0] == 1 and all(3 <= i < 259 for i in ids[1:])


@pytest.mark.parametrize("name", [m for m in MIXES if "closed" in m])
def test_first_fleet_opens_the_widest_session(name):
    mix = traffic.load_mix(TRAFFIC / f"{name}.json")
    sizes = traffic.size_set(mix)
    for seed in (0, 11, 2**31 + 1):
        fleet = take(mix, seed, int(mix["clients"]))
        assert fleet[0].output_tokens == max(o for _, o in sizes)
        assert max(p.prompt_tokens for p in fleet) == max(p for p, _ in sizes)
        assert all(p.due_s == 0.0 for p in fleet)


def test_lognormal_quantiles_keep_the_median():
    mix = traffic.load_mix(TRAFFIC / "decode-closed.json")
    sizes = traffic.size_set(mix)
    assert statistics.median(p for p, _ in sizes) == pytest.approx(160, abs=2)
    assert statistics.median(o for _, o in sizes) == pytest.approx(192, abs=3)


@pytest.mark.parametrize("name,burst", [("dry-open", 1), ("dry-bursty", 3)])
def test_open_loop_arrivals_hold_the_rate(name, burst):
    mix = traffic.load_mix(TRAFFIC / f"{name}.json")
    n = int(mix["set_size"]) * burst
    reqs = take(mix, 3, n)
    due = [p.due_s for p in reqs]
    assert due == sorted(due)
    assert n / due[-1] == pytest.approx(mix["rate_per_s"], rel=0.08)
    groups = [len(list(g)) for _, g in itertools.groupby(due)]
    assert set(groups) == {burst}


def test_dry_overrides_replace_the_mix():
    full = traffic.load_mix(TRAFFIC / "decode-closed.json")
    dry = traffic.load_mix(TRAFFIC / "decode-closed.json", dry=True)
    assert dry["clients"] < full["clients"] and dry["arrival"] == full["arrival"]


@pytest.mark.parametrize("name,seed,sha256", [
    ("chat-closed", 1, "34fc0beffa22f8f3955ab4aeacbb85450dd1d2713c26ddd583f0eab34964e390"),
    ("decode-closed", 2, "72becd8ef4133521e17742df9c229cf3b10de4da3d45d03b6eef03ece9b6c234"),
])
def test_a_mix_without_prompt_text_sends_what_it_sent_before(name, seed, sha256):
    """The first 64 prompts, joined by newlines, hashed on the tree before
    ``prompt_text`` existed (PR 26): the cells' traffic is what it was."""
    mix = traffic.load_mix(TRAFFIC / f"{name}.json")
    assert "prompt_text" not in mix
    prompts = [p.prompt for p in take(mix, seed, 64)]
    assert hashlib.sha256("\n".join(prompts).encode()).hexdigest() == sha256


TOPICS = traffic.load_mix(TRAFFIC / "dry-topics.json")


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_007])
def test_topics_follow_the_zipf_law_and_write_in_their_own_letters(seed):
    text = TOPICS["prompt_text"]
    shares = traffic.topic_shares(text)
    assert sum(shares) == pytest.approx(1.0) and shares == sorted(shares, reverse=True)
    assert shares[0] / shares[1] == pytest.approx(2 ** text["zipf"])
    n = int(TOPICS["set_size"]) * 8
    reqs = take(TOPICS, seed, n)
    drawn = collections.Counter(traffic.topic_of(TOPICS, seed, r.index) for r in reqs)
    # the hottest topic takes the share the law gives, within three deviations of the draw
    noise = 3 * math.sqrt(shares[0] * (1 - shares[0]) / n)
    assert drawn[0] / n == pytest.approx(shares[0], abs=noise)
    assert drawn.most_common(1)[0][0] == 0 and set(drawn) <= set(range(int(text["topics"])))
    for r in reqs:
        letters = traffic.topic_letters(TOPICS, traffic.topic_of(TOPICS, seed, r.index))
        assert set(r.prompt) <= set(letters) and len(r.prompt) == r.prompt_tokens - 1


def test_a_topics_letters_come_from_the_mix_not_the_seed():
    text = TOPICS["prompt_text"]
    letters = [traffic.topic_letters(TOPICS, t) for t in range(int(text["topics"]))]
    assert all(len(set(x)) == text["letters"] and set(x) <= set("abcdefghijklmnopqrstuvwxyz ") for x in letters)
    assert len(set(letters)) == len(letters)
    assert letters != [traffic.topic_letters({**TOPICS, "pairing_seed": 6}, t) for t in range(len(letters))]
    # two seeds put a hot topic's requests elsewhere and let them say the same kind of thing
    first = {s: next(r for r in take(TOPICS, s, 64) if traffic.topic_of(TOPICS, s, r.index) == 0) for s in (1, 2)}
    assert first[1].prompt != first[2].prompt and set(first[1].prompt) | set(first[2].prompt) <= set(letters[0])
