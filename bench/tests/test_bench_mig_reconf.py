"""The ``migdgx-reconf`` cell: a DGX A100 whose MIG layout the repartition
coordinator reshapes online, declared only in data files, with its
placement check and the readers of the repartition layer."""
import time
from collections import namedtuple
from types import SimpleNamespace

import pytest

import harness
from bench_cells import tiny_cell

CHECK = harness.check_reader("mig_placement")
GIB = float(1 << 30)
#: the fields of a layout-log record, as the check and the readers use them
Record = namedtuple("Record", "t move layout live")


@pytest.fixture(scope="module")
def traced():
    """One traced run of the cell shrunk to 2 GPUs, and its result line."""
    cell = tiny_cell("migdgx-reconf", rate=1.0)
    res = harness.measure(cell, 2718281828459045, 2.0, True,
                          t_proc0=time.perf_counter(), require_tpu=False)
    return cell, res


def test_the_cell_is_declared_in_data_files():
    cell = harness.resolve_cell("migdgx-reconf")
    got = harness.service_components(cell.config)
    assert got["repartition"].layout == "a100-80gb"
    assert got["repartition"].name == "frag"
    assert cell.config["checks"] == ["mig_placement"]
    base = harness.resolve_cell("migdgx-steady").config
    for key in ("slices", "flops_per_s_per_chip", "policy", "scoring",
                "age_tau", "clearing", "global_assignment", "theta"):
        assert cell.config[key] == base[key], key


def test_tiny_cell_reshapes_and_passes_every_check():
    cell = tiny_cell("migdgx-reconf", rate=1.0)
    run = harness.drive(cell, 3141592653589793, 2.0, trace=False,
                        t_proc0=time.perf_counter(), require_tpu=False)
    coord = run.service.repartition
    assert run.rounds >= 24
    assert coord.n_merges + coord.n_splits > 0
    assert len(coord.layout_log) > 1
    got = harness.check_outputs(run)
    assert got["illegal_layouts"] == {"value": 0, "limit": 0}
    assert got["inventory_mismatch"] == {"value": 0, "limit": 0}
    assert all(c["value"] <= c["limit"] for c in got.values()), got
    # jobs whose θ-floor is a whole GPU were awarded: on a 7g.80gb
    big = {j for j, d in run.jobs.items() if d.steady_bytes > 45 * GIB}
    assert any(r.job_id in big and "7g.80gb" in r.slice_id
               for r in run.service.award_log)


def _record(layout, live=None, move=None):
    if live is None:  # the scheduler holds what the layout holds
        table = {"1g.10gb": (1, 10), "1g.20gb": (1, 20), "2g.20gb": (2, 20),
                 "3g.40gb": (3, 40), "4g.40gb": (4, 40), "7g.80gb": (7, 80)}
        live = tuple((sid, table[n][0], table[n][1] * GIB)
                     for sid, _, n, _ in layout)
    return SimpleNamespace(t=0.0, move=move, layout=tuple(layout), live=live)


def _run(*records, final=None, counted=None):
    """A run whose coordinator logged ``records``, whose scheduler ends
    with ``final`` (default: the last record's live slices) and whose
    counters count ``counted`` moves (default: the logged ones)."""
    if final is None:
        final = records[-1].live if records else ()
    if counted is None:
        counted = sum(1 for r in records if r.move is not None)
    slices = {sid: SimpleNamespace(spec=SimpleNamespace(
        n_chips=n, capacity_bytes=cap)) for sid, n, cap in final}
    return SimpleNamespace(
        scheduler=SimpleNamespace(slices=slices),
        service=SimpleNamespace(repartition=SimpleNamespace(
            layout_log=list(records), n_splits=counted, n_merges=0)))


DECLARED = [("a", 0, "3g.40gb", 4), ("b", 0, "2g.20gb", 0),
            ("c", 0, "1g.10gb", 2), ("d", 0, "1g.10gb", 3)]


def test_a_legal_log_reads_zero():
    got = CHECK(_run(_record(DECLARED),
                     _record([("g", 0, "7g.80gb", 0),
                              ("h", 1, "4g.40gb", 0),
                              ("i", 1, "2g.20gb", 4),
                              ("j", 1, "1g.10gb", 6)], move="merge")))
    assert got == {"illegal_layouts": {"value": 0, "limit": 0},
                   "inventory_mismatch": {"value": 0, "limit": 0}}


@pytest.mark.parametrize("layout", [
    [("a", 0, "3g.40gb", 2)],
    [("a", 0, "1g.10gb", 7)],
    [("a", 0, "4g.40gb", 0), ("b", 0, "3g.40gb", 4), ("c", 1, "1g.10gb", 0),
     ("d", 0, "1g.20gb", 6)],
    [("a", 0, "2g.20gb", 0), ("b", 0, "1g.10gb", 1)],
    [("a", 0, "4g.40gb", 0), ("b", 0, "3g.40gb", 4)],
], ids=["3g-at-slot-2", "1g.10gb-at-slot-7", "8-compute-slices",
        "shared-memory-slot", "4g-beside-3g"])
def test_an_illegal_layout_reads_not_correct(layout):
    got = CHECK(_run(_record(DECLARED), _record(layout, move="split")))
    assert got["illegal_layouts"]["value"] == 1
    assert got["illegal_layouts"]["value"] > got["illegal_layouts"]["limit"]


def test_a_slice_the_layout_lacks_reads_not_correct():
    rec = _record(DECLARED)
    extra = _record(DECLARED, live=rec.live + (("e", 1, 10 * GIB),))
    got = CHECK(_run(rec, extra, final=rec.live))
    assert got["illegal_layouts"]["value"] == 0
    assert got["inventory_mismatch"]["value"] == 1
    # and the reverse: the layout holds an instance the scheduler lacks
    got = CHECK(_run(_record(DECLARED, live=rec.live[1:]), final=rec.live))
    assert got["inventory_mismatch"]["value"] == 1


def test_a_final_inventory_the_log_lacks_reads_not_correct():
    """The scheduler ends with a slice the last logged layout lacks: a move
    made without a record."""
    rec = _record(DECLARED)
    got = CHECK(_run(rec, final=rec.live + (("e", 1, 10 * GIB),)))
    assert got["illegal_layouts"]["value"] == 0
    assert got["inventory_mismatch"]["value"] == 1


def test_an_unlogged_move_reads_not_correct():
    """The coordinator counted a move the log does not hold, as when the
    log is full."""
    got = CHECK(_run(_record(DECLARED), counted=1))
    assert got["inventory_mismatch"]["value"] == 1


@pytest.mark.parametrize("name,log,bound", [
    ("stranded_share", "frag_trace", "MAX_TRACE"),
    ("reconfig_moves", "layout_log", "MAX_LAYOUT_LOG"),
])
def test_readers_are_silent_on_a_log_that_filled_early(name, log, bound):
    """A log that reached its bound before the window's end may have
    dropped the window's entries: its reader returns nothing, and a number
    where the full log reaches past the window."""
    cell = harness.resolve_cell("migdgx-reconf")
    start = float(cell.mix["warmup_rounds"]) * float(cell.mix["round_dt"])

    def reading(ts):
        entries = [Record(t, "merge", (), ()) if log == "layout_log"
                   else (t, 0.0) for t in ts]
        coord = SimpleNamespace(**{log: entries, bound: 3})
        run = SimpleNamespace(service=SimpleNamespace(repartition=coord),
                              rounds=10, cell=cell)
        return harness.metric_reader(name)(harness.Context(run=run,
                                                           device={}))

    assert reading([start, start + 1, start + 2]) is None
    assert reading([start, start + 1, start + 20]) is not None
    assert reading([start, start + 1]) is not None


def test_a_run_without_a_coordinator_raises():
    with pytest.raises(ValueError, match="layout log"):
        CHECK(SimpleNamespace(service=SimpleNamespace(repartition=None)))


def test_each_new_reader_returns_a_number(traced):
    cell, res = traced
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for name in ("repartition_ms", "stranded_share", "reconfig_moves"):
        assert isinstance(got.get(name), float), (name, sorted(got))
    # and so does every shared layer metric that lists the cell
    for m in cell.per_layer:
        assert isinstance(got.get(m["name"]), float), (m["name"], sorted(got))
    assert got["repartition_ms"] > 0.0
    assert 0.0 <= got["stranded_share"] <= 100.0
    assert got["reconfig_moves"] > 0.0
    assert res["correct"] is True
    assert res["checks"]["illegal_layouts"]["value"] == 0


def test_readers_are_silent_without_a_coordinator():
    run = SimpleNamespace(service=SimpleNamespace(repartition=None),
                          rounds=10, cell=harness.resolve_cell("migdgx-steady"))
    ctx = harness.Context(run=run, device={})
    for name in ("stranded_share", "reconfig_moves"):
        assert harness.metric_reader(name)(ctx) is None
