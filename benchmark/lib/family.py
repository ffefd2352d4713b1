"""A configuration brings its own model. Its file's ``family`` key (absent:
``dense``) names a module ``benchmark/families/<family>.py``, and everything
in the harness that knows what a model *is* goes through that module:

- ``REQUIRED_KEYS``: the source's keys a configuration file of this family
  must hold (read by the contract test).
- ``program_config(cfg) -> dict``: keyword arguments of the program's
  ``ModelConfig`` (``lib/system.py``, the one file that imports the program).
- ``make_weights(cfg, seed, bits)`` and ``served_logits(cfg, weights,
  token_rows, spans)``: the plain float32 reference under
  ``default_matmul_precision("highest")``, weights made from the seed by the
  configuration's stated recipe (``bits=4``: the control's). For each row of
  token ids, logits ``[n, vocab]`` at the ``n`` positions from ``first`` on
  of its span ``(first, n)``. The family owns the blocking, so that its
  longest context fits beside its weights (``lib/check.py``, which keeps the
  sampling, the comparison, the control and every limit).
- ``decode_step_bytes(cfg, rows, context_tokens)``, ``decode_token_flops(cfg,
  context)``, ``prefill_flops(cfg, prompt_tokens)``, ``weight_bytes(cfg)``,
  ``kv_bytes_per_token(cfg)``: least bytes and FLOPs from the configuration's
  shapes alone (``readers/decode_step_roofline.py``, ``readers/step_mfu.py``).

A family imports nothing of the program. A later PR adds a model by adding
``families/<name>.py`` and a configuration file that names it: no file that
is here needs an edit.
"""

from __future__ import annotations

import importlib
import re
from types import ModuleType
from typing import Any, Dict

DEFAULT = "dense"
CONTRACT = ("REQUIRED_KEYS", "program_config", "make_weights", "served_logits", "decode_step_bytes",
            "decode_token_flops", "prefill_flops", "weight_bytes", "kv_bytes_per_token")
_NAME = re.compile(r"^[a-z][a-z0-9_]{0,63}$")


def load(cfg: Dict[str, Any]) -> ModuleType:
    """The module of the configuration's family, checked against the contract."""
    name = str(cfg.get("family", DEFAULT))
    if not _NAME.match(name):
        raise ValueError(f"family {name!r}: a module's name, lower case letters, digits and '_'")
    module = importlib.import_module(f"..families.{name}", __package__)
    missing = [n for n in CONTRACT if not hasattr(module, n)]
    if missing:
        raise ImportError(f"family {name!r} ({module.__file__}) lacks {', '.join(missing)}")
    return module
