"""Cells shrunk to a size a CPU test run holds."""


#: a deployment no cell declares, so that the harness's doors for the burst
#: process and a named admission policy run end to end in tests: a cell's
#: jobs in two-state MMPP bursts (3x for ~4 of every ~40 rounds) through a
#: token bucket.  ``tiny_cell("<cell>+burst")`` lays it over the cell's mix
BURST = {"arrivals": {"process": "burst", "rate_quiet": 5.5,
                      "rate_burst": 16.5, "mean_dwell_quiet": 36.0,
                      "mean_dwell_burst": 4.0},
         "admission": {"policy": "token_bucket", "rate": 8.0, "burst": 96.0}}


def burst_cell(name: str):
    """The cell ``name`` with its mix's arrivals and admission replaced by
    :data:`BURST`, named ``"<name>+burst"``."""
    import copy

    import harness

    cell = harness.resolve_cell(name)
    cell.mix.update(copy.deepcopy(BURST))
    cell.name = f"{name}+burst"
    return cell


def tiny_cell(name: str, rate: float = 3.0):
    """A cell shrunk to a size a CPU test run holds: a few slices of each
    kind, a few arrivals a round (a Poisson mix's rate; other processes
    keep theirs), a short warm-up.  ``"<cell>+burst"`` is the cell with
    :data:`BURST` laid over its mix."""
    import harness

    base, _, overlay = name.partition("+")
    cell = burst_cell(base) if overlay == "burst" else harness.resolve_cell(name)
    for group in cell.config["slices"]:
        group["count"] = min(int(group["count"]), 2)
    if cell.mix["arrivals"].get("process", "poisson") == "poisson":
        cell.mix["arrivals"]["rate"] = rate
    cell.mix["warmup_rounds"] = 4
    cell.mix["check_rounds"] = 2
    return cell


def run_rounds(cell, seed: int, rounds: int):
    """Build the cell's system and run ``rounds`` rounds from its set-up, as
    the harness does; returns (scheduler, service, stream, the variant ids
    each round's settle selected)."""
    import harness

    state = {"n": 0}

    def on_pull(now):
        if state["n"] >= rounds:
            raise harness.StopWindow
        state["n"] += 1

    sched, svc, stream = harness.build_system(cell, harness.seeds(seed),
                                              on_pull)
    selected = []
    settle = sched._settle_round

    def keep(prep):
        rr = settle(prep)
        selected.append(tuple(v.variant_id for v in rr.selected) if rr else ())
        return rr

    sched._settle_round = keep
    try:
        svc.run(t_end=harness.HORIZON)
    except harness.StopWindow:
        pass
    return sched, svc, stream, selected
