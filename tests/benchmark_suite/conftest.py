"""The benchmark's own tests run on the CPU: no chip, tiny sizes."""

import copy
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# what the next configuration's PR appends: a metric at the end of ``per_layer`` that lists a cell of its own
APPENDED = {"name": "new.metric_ms_per_step", "unit": "ms/step", "better": "lower", "source": "device_trace",
            "layer": "model step", "moves": "tokens_per_s", "workloads": ["new-model.new-mix"]}


@pytest.fixture(params=["committed", "one_metric_appended"])
def bench(request):
    """``BENCHMARK.json`` as committed, and a copy that has one more per-layer
    metric at the end: a check that holds on both reads metrics by name and
    depends on no position in the list."""
    out = json.loads((ROOT / "BENCHMARK.json").read_text())
    if request.param == "one_metric_appended":
        out["per_layer"].append(copy.deepcopy(APPENDED))
    return out
