"""A run whose timed path is broken underneath must read as not correct.

Each test skips only the harness's look for a chip, plants one fault in the
program, drives a whole run of a shrunk cell and checks the result line.
The cells run on one chip, so the fault of an exchange between chips does
not arise.
"""
import time

import pytest

import harness
from bench_cells import tiny_cell
from repro.core.policy import GlobalAssignment, GreedyWIS
from repro.core.scoring import ScoreHandle
from repro.core.types import RoundResult

CELLS = ["pod256-steady", "migdgx-steady", "pod256-steady+burst"]


def _measure(name):
    cell = tiny_cell(name)
    return harness.measure(cell, 977, 1.0, False,
                           t_proc0=time.perf_counter(), require_tpu=False)


def _fault_scores(monkeypatch, damage):
    orig = ScoreHandle.result

    def result(self):
        out = orig(self)
        if len(out) and not getattr(self, "_bench_fault", False):
            damage(out)
            self._bench_fault = True
        return out

    monkeypatch.setattr(ScoreHandle, "result", result)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _measure(name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_round_that_commits_nothing(monkeypatch, name):
    """A step that returns its state unchanged: the settle awards nothing."""
    def settle(self, windows, fit, win_idx, scores, **kw):
        from repro.core.types import ClearingResult

        res = tuple(ClearingResult(window=w, selected=(), scores=(),
                                   total_score=0.0, n_bids=0)
                    for w in windows)
        return RoundResult(windows=tuple(windows), results=res, selected=(),
                           scores=(), total_score=0.0, n_bids=len(fit))

    monkeypatch.setattr(GreedyWIS, "settle", settle)
    monkeypatch.setattr(GlobalAssignment, "settle", settle)
    res = _measure(name)
    assert not res["correct"]
    assert res["checks"]["award_diff"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_half_the_bids_left_unscored(monkeypatch, name):
    """Half of the batch left out: the second half's scores never computed."""
    def damage(out):
        out[len(out) // 2:] = 0.0

    _fault_scores(monkeypatch, damage)
    res = _measure(name)
    assert not res["correct"]
    assert res["checks"]["score_gap"]["value"] > 1e-3


@pytest.mark.parametrize("name", CELLS)
def test_one_score_altered(monkeypatch, name):
    """An answer altered where it is produced: one bid's score moves."""
    def damage(out):
        out[0] += 1e-3

    _fault_scores(monkeypatch, damage)
    res = _measure(name)
    assert not res["correct"]
    assert res["checks"]["score_gap"]["value"] >= 1e-3 - 1e-9


@pytest.mark.parametrize("name", CELLS)
def test_one_award_dropped(monkeypatch, name):
    """An answer altered where it is produced: one window loses a winner."""
    import dataclasses

    orig_g, orig_a = GreedyWIS.settle, GlobalAssignment.settle

    def wrap(orig):
        def settle(self, *args, **kw):
            rr = orig(self, *args, **kw)
            for k, res in enumerate(rr.results):
                if res.selected:
                    results = list(rr.results)
                    results[k] = dataclasses.replace(
                        res, selected=tuple(res.selected[1:]),
                        scores=tuple(res.scores[1:]))
                    rr.results = tuple(results)
                    rr.selected = tuple(v for r in results
                                        for v in r.selected)
                    rr.selected_idx = ()
                    break
            return rr
        return settle

    monkeypatch.setattr(GreedyWIS, "settle", wrap(orig_g))
    monkeypatch.setattr(GlobalAssignment, "settle", wrap(orig_a))
    res = _measure(name)
    assert not res["correct"]
    assert res["checks"]["window_selection_diff"]["value"] > 0
