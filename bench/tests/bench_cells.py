"""Cells shrunk to a size a CPU test run holds."""


def tiny_cell(name: str, rate: float = 3.0):
    """A cell shrunk to a size a CPU test run holds: a few slices of each
    kind, a few arrivals a round, a short warm-up."""
    import harness

    cell = harness.resolve_cell(name)
    for group in cell.config["slices"]:
        group["count"] = min(int(group["count"]), 2)
    cell.mix["arrivals"]["rate"] = rate
    cell.mix["warmup_rounds"] = 4
    cell.mix["check_rounds"] = 2
    return cell
