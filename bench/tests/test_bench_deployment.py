"""A cell declares its deployment in its data files: service components in
the configuration's ``"service"`` block, admission in the mix, extra checks
in the configuration's ``"checks"``.  The harness resolves each by name,
and what a cell does not declare is built as before."""
import hashlib
import time

import pytest

import harness
from bench_cells import burst_cell, run_rounds, tiny_cell
from repro.core.repartition import FragmentationAware, MigrationConfig
from repro.service.admission import AcceptAll, TokenBucket

#: 200 rounds of each cell from ``build_system`` on seed 20261018, as the
#: tree before service blocks, named admission and the burst process gave
#: them: the hash of every job drawn (id, arrival, work, steady bytes,
#: deadline) and of every round's selected variant ids
DECISIONS = {
    "pod256-steady": ("081cf13aa401d318", "ea61c9d1fd3c5903", 1292),
    "migdgx-steady": ("eef06248e7a7ff66", "e8c930e127d31c92", 486),
}


def _hash(x) -> str:
    return hashlib.sha256(repr(x).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(DECISIONS))
def test_undeclared_cells_decide_as_before(name):
    sched, svc, stream, selected = run_rounds(harness.resolve_cell(name),
                                              20261018, 200)
    assert svc.cfg.repartition is None and svc.cfg.migration is None
    assert isinstance(svc.admission, AcceptAll)
    draws = [(d.job_id, d.t, d.work, d.steady_bytes, d.deadline)
             for d in stream.jobs.values()]
    assert len(selected) == 200
    assert (_hash(draws), _hash(selected), len(draws)) == DECISIONS[name]


def test_admission_is_resolved_by_name_with_its_params():
    policy = harness.build_admission(burst_cell("pod256-steady").mix)
    assert type(policy) is TokenBucket
    assert (policy.rate, policy.burst) == (8.0, 96.0)


def test_service_block_builds_the_declared_components():
    got = harness.service_components({"service": {
        "repartition": {"policy": "frag",
                        "params": {"split_queue_factor": 2.0}},
        "repartition_dt": 5.0,
        "migration": {"migration_budget": 2}}})
    assert got["repartition"] == FragmentationAware(split_queue_factor=2.0)
    assert got["repartition_dt"] == 5.0
    assert got["migration"] == MigrationConfig(migration_budget=2)
    assert harness.service_components({}) == {}


@pytest.mark.parametrize("kind", ["repartition", "admission", "process"])
def test_an_unknown_name_raises_naming_what_exists(kind):
    cell = harness.resolve_cell("pod256-steady")
    if kind == "repartition":
        cell.config["service"] = {"repartition": {"policy": "tetris"}}
        have = ["energy", "frag", "static"]
    elif kind == "admission":
        cell.mix["admission"] = {"policy": "lottery"}
        have = ["accept_all", "bounded_queue", "token_bucket"]
    else:
        cell.mix["arrivals"] = {"process": "diurnal", "rate": 1.0}
        have = ["burst", "poisson"]
    with pytest.raises(ValueError) as err:
        harness.build_system(cell, harness.seeds(1), None)
    for name in have:
        assert repr(name) in str(err.value)


def test_an_unknown_service_key_raises():
    with pytest.raises(ValueError, match="repartiton"):
        harness.service_components({"service": {"repartiton": {}}})


FIXTURE_CHECK = '''
def check(run):
    assert run.scheduler is not None and run.service is not None
    return {"fixture_gap": {"value": %r, "limit": 0.5}}
'''


@pytest.mark.parametrize("value,correct", [(0.25, True), (1.0, False)])
def test_a_declared_check_joins_correct(monkeypatch, tmp_path, value,
                                        correct):
    (tmp_path / "fixture.py").write_text(FIXTURE_CHECK % value)
    monkeypatch.setattr(harness, "CHECKS", tmp_path)
    cell = tiny_cell("pod256-steady")
    cell.config["checks"] = ["fixture"]
    res = harness.measure(cell, 4242424242, 1.0, False,
                          t_proc0=time.perf_counter(), require_tpu=False)
    assert res["checks"]["fixture_gap"] == {"value": value, "limit": 0.5}
    assert "score_gap" in res["checks"]
    assert res["correct"] is correct


def test_a_missing_check_module_raises(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "CHECKS", tmp_path)
    with pytest.raises(ValueError, match="no check 'absent'"):
        harness.check_reader("absent")


def test_a_repartitioned_pod_still_passes_the_reference():
    """FragmentationAware splits the pod's slices under a crowded queue; the
    reference reads each sampled round's window capacities from the round's
    record, so rounds on the new inventory compare as before."""
    cell = tiny_cell("pod256-steady", rate=12.0)
    cell.config["service"] = {"repartition": {"policy": "frag"},
                              "migration": {}}
    before = {s.slice_id for s in harness.build_slices(cell.config)}
    run = harness.drive(cell, 1618033988749, 1.5, trace=False,
                        t_proc0=time.perf_counter(), require_tpu=False)
    assert run.service.repartition.n_splits > 0
    assert run.service.migration is not None
    assert set(run.scheduler.slices) != before
    assert any(w.slice_id not in before
               for r in run.sampled for w in r["windows"])
    got = harness.check_outputs(run)
    assert all(c["value"] <= c["limit"] for c in got.values()), got
