"""A whole rehearsed run with the timed path broken underneath must come out
not correct, once per fault a one-chip router can have; a sound run must
come out correct.  Run by hand on the CPU:

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest -q benchmarks/chip

`--rehearse` skips the harness's look for a chip and uses a short stream; the
rest of the run (set-up, window, reference check) is the benchmark's own.
A fault of the exchange between chips cannot occur in these one-chip cells.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import run_cell

CELLS = ["wp_pkg_w100.saturate", "wp_wchoices_w100.saturate"]


def _run(cell: str, seed: int) -> dict:
    return run_cell.run([
        "--workload", cell, "--seed", str(seed), "--seconds", "0.5",
        "--trace", "0", "--rehearse",
    ])


@pytest.fixture
def driver():
    from repro.parallel import chunked_driver

    return chunked_driver


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _run(cell, 3_000_000_007)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_keeps_its_state(cell, driver, monkeypatch):
    """The step routes but hands back the carry it was given."""
    real = driver._get_step

    def frozen(cfg):
        step = real(cfg)

        def keep(carry, *args):
            _, choices = step(jax.tree.map(jnp.copy, carry), *args)
            return carry, choices

        return keep

    monkeypatch.setattr(driver, "_get_step", frozen)
    out = _run(cell, 11)
    assert not out["correct"]
    assert out["checks"]["loads_row_gap"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_each_chunk_left_out(cell, driver, monkeypatch):
    """The sink is handed only the first half of each chunk."""
    real = driver.ChunkedRouter._emit

    def half(pending, outs, on_chunk):
        choices, n = pending
        real((choices, n // 2), outs, on_chunk)

    monkeypatch.setattr(driver.ChunkedRouter, "_emit", staticmethod(half))
    out = _run(cell, 12)
    assert not out["correct"]
    assert out["checks"]["chunk_faults"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_one_answer_altered(cell, driver, monkeypatch):
    """The step moves one assignment of every piece, lane 17, to the next
    worker where it produces them; its carried state stays sound."""
    real = driver._get_step

    def altered(cfg):
        step = real(cfg)

        def wrong(carry, *args):
            carry, choices = step(carry, *args)
            return carry, choices.at[17].set((choices[17] + 1) % cfg.n_workers)

        return wrong

    monkeypatch.setattr(driver, "_get_step", altered)
    out = _run(cell, 13)
    assert not out["correct"]
    assert out["checks"]["mismatched_events"]["value"] > 0
    assert out["checks"]["loads_row_gap"]["value"] == 0  # only the answers tell
