"""The reader of bid collection's chain reuse share, on synthetic audit
rows and on a short traced CPU run."""
import time
from types import SimpleNamespace

import pytest

import harness
from bench_cells import tiny_cell


def _ctx(rows):
    run = SimpleNamespace(round_log=rows, rounds=len(rows))
    return harness.Context(run=run, device={})


def test_bid_chain_reuse_share_reads_the_reused_groups():
    read = harness.metric_reader("bid_chain_reuse_share")
    rows = [SimpleNamespace(n_bid_groups=40, n_bid_chains=10),
            SimpleNamespace(n_bid_groups=0, n_bid_chains=0),
            SimpleNamespace(n_bid_groups=60, n_bid_chains=15)]
    assert read(_ctx(rows)) == pytest.approx(75.0)
    # every group its own chain: nothing reused
    assert read(_ctx([SimpleNamespace(n_bid_groups=5,
                                      n_bid_chains=5)])) == 0.0


@pytest.mark.parametrize("rows", [
    # rows without the fields (a program that lacks them)
    [SimpleNamespace(n_reclears=4, n_reclears_vec=4)],
    # no rows in the window
    [],
    # no biddable agent in the whole window
    [SimpleNamespace(n_bid_groups=0, n_bid_chains=0)] * 3,
], ids=["no-field", "empty", "no-groups"])
def test_bid_chain_reuse_share_reads_nothing_without_groups(rows):
    assert harness.metric_reader("bid_chain_reuse_share")(_ctx(rows)) is None


@pytest.mark.parametrize("name", ["pod256-steady", "migdgx-reconf"])
def test_traced_run_reports_the_bid_chain_reuse_share(name):
    cell = tiny_cell(name)
    # a trace directory of its own, apart from other test files' traced
    # runs of the same cell
    cell.name = name + ".chain-reuse"
    res = harness.measure(cell, 3141592653589, 1.0, True,
                          t_proc0=time.perf_counter(), require_tpu=False)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert "bid_chain_reuse_share" in got, sorted(got)
    assert 0.0 <= got["bid_chain_reuse_share"] < 100.0
