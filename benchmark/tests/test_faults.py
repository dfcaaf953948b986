"""A whole run (set-up, window, comparison) on the CPU at a toy size, with
the timed path broken underneath: `correct` must come out false for each
fault the cells can have. The chip check is skipped (allow_cpu)."""

import jax
import numpy as np
import pytest

from benchmark.tests.helpers import run_cell


def alter_answer(traffic, results):
    results[-1]["plan"].best_count += 1


def alter_placements(traffic, results):
    for r in results:
        r["plan"].nodes_per_scenario = np.roll(r["plan"].nodes_per_scenario, 1, axis=1)


def half_lanes(traffic, results):
    """Half of the lanes answered by the other half's placements."""
    for r in results:
        nodes = np.array(r["plan"].nodes_per_scenario)
        half = nodes.shape[0] // 2
        nodes[half:2 * half] = nodes[:half]
        r["plan"].nodes_per_scenario = nodes


def test_sound_run_is_correct(capsys, monkeypatch):
    out = run_cell(capsys, monkeypatch, "pools5k.sweep64")
    assert out["correct"] is True
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("cell,fault", [
    ("pools5k.sweep64", alter_answer), ("pools5k.sweep64", alter_placements),
    ("pools5k.sweep64", half_lanes), ("spread5k.sweep64", alter_placements),
    ("pools5k.bisect8", alter_answer), ("pools5k.bisect8", alter_placements)])
def test_broken_results_are_not_correct(capsys, monkeypatch, cell, fault):
    assert run_cell(capsys, monkeypatch, cell, fault)["correct"] is False


def test_a_step_that_keeps_its_state_is_not_correct(capsys, monkeypatch):
    """The scan and wave step returns the state it was given: every pod
    is judged against the empty cluster."""
    from open_simulator_tpu.engine import scheduler
    from open_simulator_tpu.engine.exec_cache import EXEC_CACHE

    real = scheduler._step

    def stale(*args):
        state = args[-2]
        return state, real(*args)[1]

    monkeypatch.setattr(scheduler, "_step", stale)
    jax.config.update("jax_enable_compilation_cache", False)
    EXEC_CACHE.clear()
    jax.clear_caches()
    try:
        assert run_cell(capsys, monkeypatch, "spread5k.sweep64")["correct"] is False
    finally:
        monkeypatch.undo()
        EXEC_CACHE.clear()
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", True)
