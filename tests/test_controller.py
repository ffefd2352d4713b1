"""End-to-end lifecycle through ExperimentController with a toy config."""

import multiprocessing
import os

import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.config import (
    ExperimentConfig,
    OperationType,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.controller import (
    ExperimentController,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.errors import (
    ConfigError,
    RunFailedError,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.factors import (
    Factor,
    RunTableModel,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.persistence import RunTableStore
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.progress import RunProgress


class ToyConfig(ExperimentConfig):
    name = "toy"
    time_between_runs_in_ms = 0
    isolate_runs = False

    def __init__(self, out):
        self.results_output_path = out
        self.trace = []

    def create_run_table_model(self):
        return RunTableModel(
            factors=[Factor("x", [1, 2]), Factor("y", ["a"])],
            repetitions=2,
            data_columns=["product"],
        )

    def before_experiment(self):
        self.trace.append("before_experiment")

    def before_run(self, ctx):
        self.trace.append(f"before_run:{ctx.run_id}")

    def start_run(self, ctx):
        self.trace.append("start_run")

    def start_measurement(self, ctx):
        self.trace.append("start_measurement")

    def interact(self, ctx):
        self.trace.append("interact")

    def stop_measurement(self, ctx):
        self.trace.append("stop_measurement")

    def stop_run(self, ctx):
        self.trace.append("stop_run")

    def populate_run_data(self, ctx):
        return {"product": ctx.factor("x") * 10}

    def after_experiment(self):
        self.trace.append("after_experiment")


def test_full_lifecycle_inline(tmp_path):
    config = ToyConfig(tmp_path)
    ctrl = ExperimentController(config, echo=False)
    ctrl.do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    assert len(rows) == 4
    assert all(r["__done"] == RunProgress.DONE for r in rows)
    assert {r["product"] for r in rows} == {10, 20}
    # lifecycle order for the first run
    first = config.trace[: config.trace.index("stop_run") + 1]
    assert first == [
        "before_experiment",
        "before_run:run_0_repetition_0",
        "start_run",
        "start_measurement",
        "interact",
        "stop_measurement",
        "stop_run",
    ]
    assert config.trace[-1] == "after_experiment"
    # per-run artifact dirs exist (reference IRunController.py:20-21)
    assert (tmp_path / "toy" / "run_0_repetition_0").is_dir()


def test_full_lifecycle_isolated_subprocess(tmp_path):
    if multiprocessing.get_start_method(allow_none=True) != "fork":
        multiprocessing.set_start_method("fork", force=True)

    class IsolatedConfig(ToyConfig):
        isolate_runs = True

        def populate_run_data(self, ctx):
            return {"product": ctx.factor("x") * 10 + os.getpid() * 0}

    config = IsolatedConfig(tmp_path)
    ExperimentController(config, echo=False).do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    assert all(r["__done"] == RunProgress.DONE for r in rows)
    assert {r["product"] for r in rows} == {10, 20}


def test_isolated_run_refuses_to_fork_once_the_process_holds_a_tpu(
    tmp_path, monkeypatch
):
    """A chip belongs to one process: forking a run after the parent
    initialised a TPU backend must be a clear ExperimentError up front,
    never a child that hangs on its first JAX call."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner import isolation
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.errors import (
        ExperimentError,
    )

    assert not isolation._holds_tpu()  # the CPU suite holds no chip
    monkeypatch.setattr(isolation, "_holds_tpu", lambda: True)

    class IsolatedConfig(ToyConfig):
        isolate_runs = True

    config = IsolatedConfig(tmp_path)
    with pytest.raises(ExperimentError, match="a chip belongs to one process"):
        ExperimentController(config, echo=False).do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    assert rows[0]["__done"] == RunProgress.FAILED
    assert "start_run" not in config.trace  # nothing ran, nothing forked


def test_resume_skips_done_rows(tmp_path):
    config = ToyConfig(tmp_path)
    ctrl = ExperimentController(config, echo=False)
    # Simulate a crash after two runs: mark them done manually.
    for row in ctrl.rows[:2]:
        ctrl.store.update_row(
            row["__run_id"], {"__done": RunProgress.DONE, "product": 99}
        )
    config2 = ToyConfig(tmp_path)
    ctrl2 = ExperimentController(config2, echo=False)
    ctrl2.do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    done_products = {r["__run_id"]: r["product"] for r in rows}
    # the two pre-done rows kept their stored value; others were computed
    assert done_products["run_0_repetition_0"] == 99
    assert done_products["run_1_repetition_0"] == 99
    assert done_products["run_0_repetition_1"] in (10, 20)
    # only two runs actually executed on resume
    assert config2.trace.count("start_run") == 2


def test_failed_run_marked_and_raises(tmp_path):
    class FailingConfig(ToyConfig):
        def interact(self, ctx):
            raise ValueError("boom in run")

    config = FailingConfig(tmp_path)
    ctrl = ExperimentController(config, echo=False)
    with pytest.raises(ValueError, match="boom in run"):
        ctrl.do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    assert rows[0]["__done"] == RunProgress.FAILED
    # after_experiment still ran (finally-block)
    assert config.trace[-1] == "after_experiment"


def test_failed_isolated_run_carries_child_traceback(tmp_path):
    if multiprocessing.get_start_method(allow_none=True) != "fork":
        multiprocessing.set_start_method("fork", force=True)

    class FailingIsolated(ToyConfig):
        isolate_runs = True

        def interact(self, ctx):
            raise ValueError("boom in child")

    ctrl = ExperimentController(FailingIsolated(tmp_path), echo=False)
    with pytest.raises(RunFailedError, match="boom in child"):
        ctrl.do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    assert rows[0]["__done"] == RunProgress.FAILED


def test_failed_run_retried_on_resume(tmp_path):
    class FailingOnce(ToyConfig):
        fail = True

        def interact(self, ctx):
            if type(self).fail:
                type(self).fail = False
                raise ValueError("transient")

    config = FailingOnce(tmp_path)
    with pytest.raises(ValueError):
        ExperimentController(config, echo=False).do_experiment()
    ctrl2 = ExperimentController(FailingOnce(tmp_path), echo=False)
    ctrl2.do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    assert all(r["__done"] == RunProgress.DONE for r in rows)


def test_validation_rejects_bad_settings(tmp_path):
    class BadConfig(ToyConfig):
        time_between_runs_in_ms = -5

    with pytest.raises(ConfigError, match="time_between_runs_in_ms"):
        ExperimentController(BadConfig(tmp_path), echo=False)

    class BadName(ToyConfig):
        name = "has/slash"

    with pytest.raises(ConfigError, match="path separators"):
        ExperimentController(BadName(tmp_path), echo=False)


def test_semi_mode_raises_continue(tmp_path):
    class SemiConfig(ToyConfig):
        operation_type = OperationType.SEMI

        def continue_experiment(self):
            self.trace.append("continue")

    config = SemiConfig(tmp_path)
    ExperimentController(config, echo=False).do_experiment()
    # No CONTINUE gate after the final run: 4 runs -> 3 gates.
    assert config.trace.count("continue") == 3
    assert config.trace[-1] == "after_experiment"


def test_isolated_child_killed_surfaces_as_run_failure(tmp_path):
    if multiprocessing.get_start_method(allow_none=True) != "fork":
        multiprocessing.set_start_method("fork", force=True)

    class DyingConfig(ToyConfig):
        isolate_runs = True

        def interact(self, ctx):
            os._exit(137)  # simulate OOM-kill: child dies without reporting

    ctrl = ExperimentController(DyingConfig(tmp_path), echo=False)
    with pytest.raises(RunFailedError, match="without reporting"):
        ctrl.do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    assert rows[0]["__done"] == RunProgress.FAILED


def test_resume_with_numeric_string_treatments(tmp_path):
    """CSV round-trip turns '32' into int 32; resume must still reconcile."""

    class StringyConfig(ToyConfig):
        def create_run_table_model(self):
            return RunTableModel(
                factors=[Factor("prompt_len", ["32", "64"]), Factor("flag", ["True"])],
                data_columns=["product"],
            )

        def populate_run_data(self, ctx):
            return {"product": 1}

    config = StringyConfig(tmp_path)
    ctrl = ExperimentController(config, echo=False)
    ctrl.store.update_row(
        ctrl.rows[0]["__run_id"], {"__done": RunProgress.DONE, "product": 7}
    )
    config2 = StringyConfig(tmp_path)
    ctrl2 = ExperimentController(config2, echo=False)
    ctrl2.do_experiment()
    rows = RunTableStore(tmp_path / "toy").read()
    assert all(r["__done"] == RunProgress.DONE for r in rows)
    # factor values in the resumed controller keep the config's types
    assert ctrl2.rows[0]["prompt_len"] == "32"
