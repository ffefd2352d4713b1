"""The one general traffic generator. A mix is a data file of parameters
(``benchmark/traffic/<mix>.json``); everything a request is made of comes
from the mix and ``--seed`` and from nothing else: its sizes, its due time
and what its prompt says (one alphabet, or the mix's ``prompt_text`` topics).

Every seed gets the SAME set of sizes and arrival gaps, in another order:
lengths are the stratified quantiles of the mix's distributions
(``set_size`` of them, paired and ordered by the mix's own
``pairing_seed``), and the seed only picks where that cyclic order is
entered (and what the prompts say). Runs with different seeds therefore do
the same work, which is what lets a few runs stand for the cell.
"""

from __future__ import annotations

import json
import math
import random
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Tuple

_ALPHABET = "abcdefghijklmnopqrstuvwxyz     etaoin"
_LETTERS = "abcdefghijklmnopqrstuvwxyz "  # what a topic's letters are chosen from


@dataclass(frozen=True)
class Planned:
    """One request before it is sent."""

    index: int  # position in the seed's order
    prompt: str  # ASCII: tokens = len(prompt) + 1 (BOS)
    prompt_tokens: int
    output_tokens: int
    due_s: float  # open loop: offset from the start of traffic; closed: 0.0


def load_mix(path: Path, dry: bool = False) -> Dict[str, Any]:
    mix = json.loads(Path(path).read_text())
    if dry and "dry" in mix:
        mix = {**mix, **mix["dry"]}
    if mix.get("arrival") not in ("closed", "open"):
        raise ValueError(f"{path}: arrival must be 'closed' or 'open'")
    return mix


def _quantiles(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` stratified draws of a clipped distribution, as whole tokens."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
    step = int(spec.get("round_to", 1))
    norm = NormalDist()
    draws = (math.exp(mu + sigma * norm.inv_cdf((i + 0.5) / n)) for i in range(n))
    return [max(lo, min(hi, round(x / step) * step)) for x in draws]


def size_set(mix: Dict[str, Any]) -> List[Tuple[int, int]]:
    """The mix's fixed multiset of (prompt tokens, output tokens): the
    same for every seed."""
    n = int(mix["set_size"])
    prompts = _quantiles(mix["prompt_tokens"], n)
    outputs = _quantiles(mix["output_tokens"], n)
    random.Random(int(mix.get("pairing_seed", 0))).shuffle(outputs)
    return list(zip(prompts, outputs))


def _prompt(seed: int, index: int, tokens: int) -> str:
    rng = random.Random((seed << 20) ^ index)
    return "".join(rng.choice(_ALPHABET) for _ in range(tokens - 1))


def topic_shares(text: Dict[str, Any]) -> List[float]:
    """The Zipf(``zipf``) law over the group's ``topics``: topic ``t``
    (from 0, the hottest) takes a share proportional to ``1 / (t + 1) ** zipf``."""
    weights = [1.0 / (t + 1) ** float(text["zipf"]) for t in range(int(text["topics"]))]
    total = sum(weights)
    return [w / total for w in weights]


def topic_letters(mix: Dict[str, Any], topic: int) -> str:
    """The ``letters`` characters a topic writes in, chosen from a-z and
    the space by the mix's ``pairing_seed`` and the topic: the same for
    every seed, so that a hot topic's tokens repeat from run to run."""
    rng = random.Random((int(mix.get("pairing_seed", 0)) << 32) | topic)
    return "".join(rng.sample(_LETTERS, int(mix["prompt_text"]["letters"])))


def _topic_prompt(mix: Dict[str, Any], seed: int, index: int, tokens: int) -> Tuple[int, str]:
    """(topic, prompt) of a request under the mix's ``prompt_text`` group:
    the request's own RNG draws its topic from the Zipf law, then its
    characters from that topic's letters."""
    rng = random.Random((seed << 20) ^ index)
    shares = topic_shares(mix["prompt_text"])
    topic = rng.choices(range(len(shares)), weights=shares)[0]
    letters = topic_letters(mix, topic)
    return topic, "".join(rng.choice(letters) for _ in range(tokens - 1))


def prompt_of(mix: Dict[str, Any], seed: int, index: int, tokens: int) -> str:
    """What request ``index`` of (mix, seed) says. With no ``prompt_text``
    group, every character comes from one alphabet; with one
    (``{"topics": n, "zipf": s, "letters": m}``), few topics are hot and a
    topic's tokens repeat: the text that uneven expert routing is made of."""
    if "prompt_text" not in mix:
        return _prompt(seed, index, tokens)
    return _topic_prompt(mix, seed, index, tokens)[1]


def topic_of(mix: Dict[str, Any], seed: int, index: int) -> int:
    """The topic request ``index`` of (mix, seed) drew."""
    return _topic_prompt(mix, seed, index, 1)[0]


def first_fleet(mix: Dict[str, Any]) -> int:
    """Requests that are in the queue together when the first session
    opens: the closed loop's clients, or an open loop's ``first_burst``."""
    if mix["arrival"] == "closed":
        return int(mix["clients"])
    return int(mix.get("first_burst", 0))


def distinct_prompts(mix: Dict[str, Any]) -> int:
    return len({p for p, _ in size_set(mix)})


def warmup_requests(mix: Dict[str, Any]) -> int:
    """Requests that have to finish before the window may open: the first
    fleet and one join of every distinct prompt length (see
    :func:`sizes_in_order`)."""
    return first_fleet(mix) + distinct_prompts(mix)


def sizes_in_order(mix: Dict[str, Any], seed: int) -> Iterator[Tuple[int, int]]:
    """The sizes of the requests of (mix, seed), in the order they are sent.

    The size set is put into one fixed cyclic order (by the mix's
    ``pairing_seed``) and the seed picks where the cycle is entered: every
    seed sends the same sizes with the same neighbours, from another point
    on. Shuffling anew for each seed moved ``tokens_per_s`` by 3-4% from
    seed to seed while two runs of one seed agreed to 0.01% (the order
    decides which joins collide), so the order is kept.

    Before the cycle comes a head that is the same for every seed, arranged
    for what the program does with first sights. The first fleet opens the
    session, whose static shapes come from it (side caches from the widest
    output bucket, the page table from the longest prompt), so the set's
    longest output and longest prompt lead it. And the program compiles a
    few small host-side programs for every distinct prompt length it
    admits, so after the fleet comes one request of each distinct length:
    warm-up, which waits for them, leaves no length unmet for the window."""
    order = size_set(mix)
    random.Random(int(mix.get("pairing_seed", 0)) + 0x51ED).shuffle(order)
    fleet = first_fleet(mix)
    if fleet:
        head = list(order)
        i = max(range(len(head)), key=lambda j: head[j][1])
        head[0], head[i] = head[i], head[0]
        if len(head) > 1:
            i = max(range(1, len(head)), key=lambda j: head[j][0])
            head[1], head[i] = head[i], head[1]
        yield from head[:fleet]
        seen = set()
        for size in head[fleet:] + head[:fleet]:
            if size[0] not in seen:
                seen.add(size[0])
                yield size
    at = seed % len(order)
    while True:
        yield order[at]
        at = (at + 1) % len(order)


def arrival_gaps(mix: Dict[str, Any], seed: int) -> Iterator[float]:
    """Open loop: seconds between one burst and the next. Stratified
    exponential quantiles at ``rate_per_s / burst`` bursts a second (a
    Poisson process with a fixed set of gaps), in one fixed cyclic order
    that the seed enters at its own point, as the sizes are."""
    burst = max(1, int(mix.get("burst", 1)))
    mean = burst / float(mix["rate_per_s"])
    n = int(mix["set_size"])
    order = [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    random.Random(int(mix.get("pairing_seed", 0)) + 0xA221).shuffle(order)
    at = (seed // n) % n  # not the sizes' entry point: the two cycles drift apart
    while True:
        yield order[at]
        at = (at + 1) % n


def planned(mix: Dict[str, Any], seed: int) -> Iterator[Planned]:
    """The endless stream of requests of (mix, seed), in the order they
    are sent. Closed loop: whichever client is free takes the next. Open
    loop: each has its due time."""
    sizes = sizes_in_order(mix, seed)
    if mix["arrival"] == "closed":
        for index, (p, o) in enumerate(sizes):
            yield Planned(index, prompt_of(mix, seed, index, p), p, o, 0.0)
        return
    burst = max(1, int(mix.get("burst", 1)))
    due, index = 0.0, 0
    for gap in arrival_gaps(mix, seed):
        due += gap
        for _ in range(burst):
            p, o = next(sizes)
            yield Planned(index, prompt_of(mix, seed, index, p), p, o, due)
            index += 1


def token_ids(prompt: str) -> List[int]:
    """The ids the program's byte tokenizer gives an ASCII prompt: BOS 1,
    then byte + 3. Stated in the configuration's ``assumed.tokenizer``."""
    return [1] + [b + 3 for b in prompt.encode("ascii")]
