"""What the program's ``sched.slice`` spans say an expert model's decode
slices did (``lib/spans.py``): per slice, the attributes the session puts
there beside ``rows`` and ``ctx_tokens`` (``engine/stepped.py``,
``MOE_COUNT_NAMES``): ``moe_held``, ``moe_zero``, ``moe_absent`` (token-expert
pairs on held, identity and absent experts) and ``moe_touched`` (held experts
with at least one pair), each summed over the slice's steps and layers, with
``moe_steps`` (steps the slice ran) and ``moe_tokens`` (tokens it produced:
``moe_held + moe_zero + moe_absent = moe_tokens x layers x moe_topk``).

A program without these attributes (an older commit, a model without an
expert layer) gives no such slice, and every reader then finds nothing.
"""

from __future__ import annotations

from typing import Any, Dict, List

from . import spans as S

SLICE = "sched.slice"
NEEDED = ("moe_held", "moe_touched", "moe_steps", "moe_tokens")


def slices(t0: float, t1: float) -> List[Dict[str, Any]]:
    """Attributes of the expert model's decode slices that lie whole inside ``[t0, t1]``."""
    return [s.attrs for s in S.finished(t0, t1)
            if s.name == SLICE and all(k in s.attrs for k in NEEDED) and s.attrs["moe_steps"] > 0]


def layers(cfg: Dict[str, Any]) -> int:
    return int(cfg["num_layers"])
