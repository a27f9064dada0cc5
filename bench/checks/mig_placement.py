"""The MIG placement check: every layout the repartition coordinator logged
is legal under the A100-80GB placement table and holds exactly the
scheduler's live slices.

The coordinator's layout log (``run.service.repartition.layout_log``) has
one record for the layout as adopted and one after every executed move:
``layout``, each instance as ``(slice id, gpu, profile name, start slot)``,
and ``live``, the scheduler's slices then as ``(slice id, n_chips,
capacity_bytes)``.  The table below is this check's own copy of the NVIDIA
MIG User Guide's A100-80GB profiles and placements (``nvidia-smi mig
-lgipp``), with the one pair of profiles that its figure of valid A100
GPU-instance combinations ("Supported MIG Profiles") never puts on one GPU,
4g + 3g; the check imports nothing of the program.  Two numbers, each 0 in
a correct run:

* ``illegal_layouts``: records whose layout puts an instance of an unknown
  profile or off its legal starts, shares a memory slot between two
  instances of a GPU, gives a GPU more than 7 compute slices, or puts an
  excluded pair on one GPU;
* ``inventory_mismatch``: over the records, the slices ``(id, n_chips,
  capacity)`` that the layout holds and the scheduler does not, or the
  reverse; the same between the last record and the scheduler's slices at
  the end of the run; and the moves the coordinator counted
  (``n_splits + n_merges``) that have no record, as when the log is full.
"""
from __future__ import annotations

GIB = float(1 << 30)
COMPUTE_SLICES = 7

#: profile: (compute slices of 7, memory slots of 8, GiB, legal start slots)
A100_80GB = {
    "1g.10gb": (1, 1, 10, (0, 1, 2, 3, 4, 5, 6)),
    "1g.20gb": (1, 2, 20, (0, 2, 4, 6)),
    "2g.20gb": (2, 2, 20, (0, 2, 4)),
    "3g.40gb": (3, 4, 40, (0, 4)),
    "4g.40gb": (4, 4, 40, (0,)),
    "7g.80gb": (7, 8, 80, (0,)),
}
#: profiles that never share a GPU
EXCLUDED = ({"4g.40gb", "3g.40gb"},)


def illegal(layout) -> bool:
    """A layout breaks the table on some GPU."""
    slots, compute, names = {}, {}, {}
    for _, gpu, name, start in layout:
        if name not in A100_80GB:
            return True
        c, mem, _, starts = A100_80GB[name]
        if start not in starts:
            return True
        used = slots.setdefault(gpu, set())
        mine = set(range(start, start + mem))
        if used & mine:
            return True
        used |= mine
        compute[gpu] = compute.get(gpu, 0) + c
        names.setdefault(gpu, set()).add(name)
    return (any(c > COMPUTE_SLICES for c in compute.values())
            or any(pair <= on_gpu for on_gpu in names.values()
                   for pair in EXCLUDED))


def held(layout) -> set:
    """The slices a layout holds, as ``(id, n_chips, GiB)``."""
    out = set()
    for sid, _, name, _ in layout:
        c, _, gib, _ = A100_80GB.get(name, (None, None, None, None))
        out.add((sid, c, gib))
    return out


def mismatch(layout, live) -> int:
    """Slices one of a layout and ``(id, n_chips, capacity_bytes)`` rows
    holds and the other does not."""
    live = {(sid, int(n), round(cap / GIB, 6)) for sid, n, cap in live}
    return len(held(layout) ^ live)


def check(run) -> dict:
    coord = getattr(run.service, "repartition", None)
    records = getattr(coord, "layout_log", None)
    if records is None:
        raise ValueError("the mig_placement check reads the service's "
                         "repartition coordinator and its layout log; the "
                         "run has none")
    off = sum(mismatch(r.layout, r.live) for r in records)
    if records:
        final = [(sid, tl.spec.n_chips, tl.spec.capacity_bytes)
                 for sid, tl in run.scheduler.slices.items()]
        off += mismatch(records[-1].layout, final)
    logged = sum(1 for r in records if r.move is not None)
    off += abs(coord.n_splits + coord.n_merges - logged)
    return {"illegal_layouts": {"value": sum(illegal(r.layout)
                                             for r in records),
                                "limit": 0},
            "inventory_mismatch": {"value": off, "limit": 0}}
