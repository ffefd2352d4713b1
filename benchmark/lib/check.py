"""The comparison that decides ``correct``: what the timed path served,
against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished (drawn from the seed, the longest always
in it) is run through the reference: one teacher-forced pass over each
prompt with its served tokens. For every served token, the gap by which
its reference logit lies below the reference's best is read; the widest
is compared with the configuration's ``check.max_logit_gap``. Greedy
decoding in exact arithmetic gives 0; bfloat16 activations move a near tie
by a little; a wrong token, a wrong weight or a corrupted cache moves it
by a lot. Counted beside it, with the limit 0: finished requests that did
not deliver exactly the tokens they asked for. One exception is the
program's own rule and not a fault: its stepped session ends a row at the
tokenizer's EOS (id 2) whatever ``stop_at_eos`` says, so a request whose
last token is EOS may be short; whether EOS was the right token there is
the logit gap's to say.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List, Sequence

from . import family
from .stats import Record
from .traffic import token_ids

EOS_ID = 2  # the byte tokenizer's, as the configuration's ``assumed`` states


def sample(finished: Sequence[Record], n: int, seed: int) -> List[Record]:
    """``n`` of the finished requests, drawn from the seed, the longest always among them."""
    done = [r for r in finished if r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_tokens + len(r.tokens), -r.index))
    rest = [r for r in done if r is not longest]
    random.Random(seed ^ 0xC0FFEE).shuffle(rest)
    return [longest] + rest[: max(0, n - 1)]


def served_logits(cfg: Dict[str, Any], seed: int, picked: Sequence[Record], bits: int = 8):
    """Reference logits, one ``[n, vocab]`` array per picked request, at
    the positions that predict its served tokens, from the reference of
    the configuration's family. The weights are made here and dropped
    before returning: a 7B model's two sets (reference and control) do
    not fit the chip together."""
    fam = family.load(cfg)
    rows = [token_ids(r.prompt) + list(r.tokens) for r in picked]
    # the token at position p is predicted by the logits at p - 1
    spans = [(r.prompt_tokens - 1, len(r.tokens)) for r in picked]
    return fam.served_logits(cfg, fam.make_weights(cfg, seed, bits), rows, spans)


def gaps_below_best(ref_logits, chosen) -> "np.ndarray":
    """Reference's best logit minus its logit of the chosen token, at
    every position of every request."""
    import jax.numpy as jnp
    import numpy as np

    out = []
    for lg, tok in zip(ref_logits, chosen):
        tok = jnp.asarray(tok, dtype=jnp.int32)
        out.append(np.asarray(jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]))
    return np.concatenate(out)


def run(cfg: Dict[str, Any], seed: int, records: Sequence[Record], t0: float, t1: float,
        control: bool = False) -> Dict[str, Any]:
    """The numbers compared, each beside its limit, and ``correct``.

    ``control`` puts the control in the program's place: at every compared
    position the token that the int4 reference puts first stands where the
    served token stood, and the same comparison judges it. A comparison
    that lets it pass decides nothing, so such a run has to end with
    ``correct`` false."""
    spec = cfg["check"]
    limit = float(spec["max_logit_gap"])
    finished = [r for r in records if r.t_done is not None and t0 <= r.t_done < t1 and r.error is None]
    wrong_length = sum(
        1 for r in finished
        if len(r.tokens) != r.output_tokens
        and not (r.tokens and r.tokens[-1] == EOS_ID and len(r.tokens) < r.output_tokens)
    )
    picked = sample(finished, int(spec["sample_requests"]), seed)
    numbers: Dict[str, Dict[str, float]] = {}
    compared = 0
    if picked:
        import jax.numpy as jnp

        ref = served_logits(cfg, seed, picked)
        chosen = [r.tokens for r in picked]
        if control:
            chosen = [jnp.argmax(lg, axis=-1) for lg in served_logits(cfg, seed, picked, bits=4)]
        gaps = gaps_below_best(ref, chosen)
        compared = int(gaps.size)
        numbers["logit_gap_max"] = {"value": float(gaps.max()), "limit": limit}
    numbers["tokens_compared"] = {"value": compared, "limit": 1, "at_least": True}
    numbers["wrong_length"] = {"value": wrong_length, "limit": 0}
    correct = all(
        (n["value"] >= n["limit"]) if n.get("at_least") else (n["value"] <= n["limit"])
        for n in numbers.values()
    )
    return {"correct": bool(correct), "numbers": numbers, "sampled": [r.index for r in picked]}
