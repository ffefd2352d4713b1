"""chip_smoke.py off the chip: its request script against a tiny CPU
server, and its refusal to pass without a TPU."""

import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

import chip_smoke  # noqa: E402

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (  # noqa: E402
    JaxEngine,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (  # noqa: E402
    get_model_config,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (  # noqa: E402
    pallas_decode_attention,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.server import (  # noqa: E402
    GenerationServer,
)

# The chip plan's mechanisms at the smallest sizes that still reach them:
# two-page anchor, a joiner past one 256-token chunk, a table wider than
# eight pages (the Pallas parts kernel, in interpret mode here).
TINY_PLAN = chip_smoke.Plan(
    short_prompt=130, short_new=20,
    join_prompt=260, join_new=4,
    long_prompt=1030, long_new=4,
    burst_prompt=130, burst_new=(3, 5),
)


@pytest.fixture
def tiny_server():
    engine = JaxEngine(
        registry={
            "tiny": get_model_config("qwen2:1.5b").tiny(max_seq_len=2048)
        },
        dtype=jnp.float32,
        paged_kv=True,
        decode_attention=pallas_decode_attention,  # stacked mode on CPU
    )
    server = GenerationServer(
        engine, host="127.0.0.1", port=0, quiet=True, scheduler="continuous"
    )
    server.start()
    yield f"http://127.0.0.1:{server.port}"
    server.stop()


def test_request_script_passes_against_a_tiny_cpu_server(tiny_server):
    lines = []
    obs = chip_smoke.run_requests(
        tiny_server, model="tiny", plan=TINY_PLAN, observe=lines.append
    )
    phases = [row["phase"] for row in obs["requests"]]
    assert phases[:4] == ["solo", "joined", "twin-streamed", "long-streamed"] or (
        phases[:4] == ["solo", "twin-streamed", "joined", "long-streamed"]
    )
    assert len(phases) == 4 + len(TINY_PLAN.burst_new)
    assert obs["flight_delta"]["join_chunk"] >= 2
    # the long request's step reports a table wider than 8 and the
    # Pallas parts kernel (interpret mode here), and no slice compiled:
    # the session compiled its step at open
    assert obs["long_session"]["attention"] == {
        "table_width": 32, "impl": "pallas",
    }
    assert obs["slices"]["count"] >= 4 and obs["slices"]["compiled"] == 0
    # CPU reports no memory_stats: the one-pool check says so, it does
    # not pass on a number it never took
    assert obs["decode_bytes_over_idle"].startswith("not measured")
    assert all(line.startswith("smoke observation:") for line in lines)


def test_request_script_fails_on_a_server_that_never_joins(tiny_server):
    """A plan whose anchor is one page wide cannot seat the joiner
    mid-flight: the script must say so, not pass."""
    plan = chip_smoke.Plan(
        short_prompt=40, short_new=20, join_prompt=260, join_new=4,
        long_prompt=1030, long_new=4, burst_prompt=40, burst_new=(3,),
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="mid-flight join"):
        chip_smoke.run_requests(
            tiny_server, model="tiny", plan=plan, observe=lambda _l: None
        )


def test_main_refuses_a_host_without_a_tpu(capsys):
    """On a CPU-only host main() exits non-zero, names the platform, and
    prints no result line — before any model is loaded."""
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err and "nothing was loaded" in err
    assert '"ok"' not in out
