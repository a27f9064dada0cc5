"""The reader of the settle's batched share, on synthetic audit rows and on
a short traced CPU run."""
import time
from types import SimpleNamespace

import pytest

import harness
from bench_cells import tiny_cell


def _ctx(rows):
    run = SimpleNamespace(round_log=rows, rounds=len(rows))
    return harness.Context(run=run, device={})


def test_settle_vector_share_reads_the_batched_share():
    read = harness.metric_reader("settle_vector_share")
    rows = [SimpleNamespace(n_reclears=10, n_reclears_vec=8),
            SimpleNamespace(n_reclears=30, n_reclears_vec=30),
            SimpleNamespace(n_reclears=0, n_reclears_vec=0),
            SimpleNamespace(n_reclears=40, n_reclears_vec=2)]
    assert read(_ctx(rows)) == 50.0
    # rows without the field (a program that lacks it), no rows, or no
    # window cleared in the whole window: nothing to read, and no error
    assert read(_ctx([SimpleNamespace(n_reclears=4)])) is None
    assert read(_ctx([])) is None
    assert read(_ctx([SimpleNamespace(n_reclears=0, n_reclears_vec=0)])) is None


@pytest.mark.parametrize("name", ["pod256-steady", "migdgx-steady",
                                  "pod256-steady+burst"])
def test_traced_run_reports_the_settle_vector_share(name):
    cell = tiny_cell(name)
    # a trace directory of its own, apart from other test files' traced
    # runs of the same cell
    cell.name = name + ".vector-share"
    res = harness.measure(cell, 2718281828459, 1.0, True,
                          t_proc0=time.perf_counter(), require_tpu=False)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert "settle_vector_share" in got, sorted(got)
    assert 0.0 <= got["settle_vector_share"] <= 100.0
