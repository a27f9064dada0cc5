"""The A100-80GB MIG layout behind the repartition coordinator.

Covers the placement table (against a brute-force enumeration of every
set of placements), adoption of a declared DGX A100 inventory, canonical
ids under split/merge cycles, the θ-floor of the pending demand, the
FragmentationAware policy over the layout under seeded random demand, and
the forced-drain path onto reshaped instances.
"""
import pytest

from repro.core import (EnergyAware, FragmentationAware, JasdaScheduler,
                        MigLayout, MigrationConfig, MigrationPlanner, Move,
                        RepartitionCoordinator, RepartitionPolicy, SimConfig,
                        SliceSpec, make_workload, simulate)
from repro.core.repartition import (A100_80GB, MIG_COMPUTE, mig_geometries,
                                    mig_legal)

GIB = float(1 << 30)

#: (name, compute slices, memory slots, legal starts): the table as the
#: MIG User Guide prints it, kept apart from the module under test
TABLE = [("1g.10gb", 1, 1, (0, 1, 2, 3, 4, 5, 6)),
         ("1g.20gb", 1, 2, (0, 2, 4, 6)),
         ("2g.20gb", 2, 2, (0, 2, 4)),
         ("3g.40gb", 3, 4, (0, 4)),
         ("4g.40gb", 4, 4, (0,)),
         ("7g.80gb", 7, 8, (0,))]
#: the pair the guide's figure of valid A100 combinations never shows on
#: one GPU, although its placements are disjoint
EXCLUDED = {"4g.40gb", "3g.40gb"}


def _reference_legal(layout):
    if EXCLUDED <= {name for name, _ in layout}:
        return False
    used, compute = set(), 0
    for name, start in layout:
        _, c, mem, starts = next(t for t in TABLE if t[0] == name)
        if start not in starts:
            return False
        slots = set(range(start, start + mem))
        if slots & used or max(slots) > 7:
            return False
        used |= slots
        compute += c
    return compute <= 7


def _dgx(gpus=8):
    """The declared dgx-a100-mig inventory: 3g.40gb + 2g.20gb + 2x 1g.10gb
    on each GPU, named as the benchmark names them."""
    out = []
    for g in range(gpus):
        out += [SliceSpec(f"gpu{g}-3g.40gb", 40 * GIB, n_chips=3),
                SliceSpec(f"gpu{g}-2g.20gb", 20 * GIB, n_chips=2),
                SliceSpec(f"gpu{g}-1g.10gb-a", 10 * GIB, n_chips=1),
                SliceSpec(f"gpu{g}-1g.10gb-b", 10 * GIB, n_chips=1)]
    return out


def _inventory_matches(coord):
    layout = {(sid, coord.state.profile(sid).compute,
               coord.state.profile(sid).capacity_bytes)
              for sid in coord.state.instances}
    live = {(sid, tl.spec.n_chips, tl.spec.capacity_bytes)
            for sid, tl in coord.scheduler.slices.items()}
    return layout == live


# ---------------------------------------------------------------------------
# the placement table
# ---------------------------------------------------------------------------

def test_table_is_the_published_one():
    assert [(p.name, p.compute, p.mem_slots, p.starts) for p in A100_80GB] \
        == TABLE
    shapes = [(p.compute, p.capacity_gib) for p in A100_80GB]
    assert len(set(shapes)) == len(shapes)  # a profile is its (n_chips, cap)


def test_layout_admits_exactly_the_brute_force_layouts():
    placements = [(name, s) for name, _, _, starts in TABLE for s in starts]
    brute = set()
    for mask in range(1 << len(placements)):
        layout = [placements[i] for i in range(len(placements))
                  if mask >> i & 1]
        ok = _reference_legal(layout)
        assert mig_legal(layout) == ok, layout
        if ok:
            brute.add(frozenset(layout))
    enumerated = {frozenset(g) for g in mig_geometries()
                  if sum(next(t[1] for t in TABLE if t[0] == n)
                         for n, _ in g) <= MIG_COMPUTE
                  and not EXCLUDED <= {n for n, _ in g}}
    assert enumerated == brute


@pytest.mark.parametrize("layout,legal", [
    ([("3g.40gb", 4), ("2g.20gb", 0), ("1g.10gb", 2), ("1g.10gb", 3)], True),
    ([("1g.10gb", s) for s in range(7)], True),
    ([("4g.40gb", 0), ("3g.40gb", 4)], False),
    ([("4g.40gb", 0), ("2g.20gb", 4), ("1g.10gb", 6)], True),
    ([("3g.40gb", 0), ("3g.40gb", 4)], True),
    ([("7g.80gb", 0)], True),
    ([("3g.40gb", 2)], False),
    ([("2g.20gb", 1)], False),
    ([("1g.10gb", 7)], False),
    ([("4g.40gb", 0), ("4g.40gb", 0)], False),
], ids=["3g+2g+1g+1g", "7x1g", "4g+3g", "4g+2g+1g", "3g+3g", "7g", "3g@2",
        "2g@1",
        "1g.10gb@7", "4g+4g"])
def test_declared_geometries(layout, legal):
    assert mig_legal(layout) is legal


# ---------------------------------------------------------------------------
# adoption and canonical ids
# ---------------------------------------------------------------------------

def test_adoption_reproduces_the_declared_geometry():
    specs = _dgx()
    layout = MigLayout.adopt(specs)
    assert layout.is_legal()
    assert MigLayout.adopt(list(reversed(specs))).instances == layout.instances
    assert set(layout.instances) == {s.slice_id for s in specs}
    for g in range(8):
        assert layout.geometry(g) == [("1g.10gb", 2), ("1g.10gb", 3),
                                      ("2g.20gb", 0), ("3g.40gb", 4)]
        assert layout.instances[f"gpu{g}-3g.40gb"] == (g, "3g.40gb", 4)


@pytest.mark.parametrize("spec,match", [
    (SliceSpec("node-3g", 40 * GIB, n_chips=3), "names no GPU"),
    (SliceSpec("gpu0-5g", 50 * GIB, n_chips=5), "no A100-80GB profile"),
])
def test_adoption_rejects_what_the_table_lacks(spec, match):
    with pytest.raises(ValueError, match=match):
        MigLayout.adopt([spec])


def test_no_move_puts_4g_beside_3g():
    """The split of a 7g.80gb offers no 4g + 3g, a merge into 4g.40gb is
    never offered beside a 3g.40gb, and neither validates."""
    layout = MigLayout.adopt([SliceSpec("gpu0-7g", 80 * GIB, n_chips=7)])
    splits = layout.splits()
    assert splits and all(not EXCLUDED <= {n for n, _ in m.into}
                          for m in splits)
    with pytest.raises(ValueError, match="illegal"):
        layout.validate(Move("split", ("gpu0-7g",),
                             into=(("4g.40gb", 0), ("3g.40gb", 4))))
    layout = MigLayout.adopt(_dgx(1))  # 3g.40gb at slot 4 beside 2g+1g+1g
    assert all(m.into[0][0] != "4g.40gb" for m in layout.merges())
    with pytest.raises(ValueError, match="illegal"):
        layout.validate(Move("merge", ("gpu0-1g.10gb-a", "gpu0-1g.10gb-b",
                                       "gpu0-2g.20gb"),
                             into=(("4g.40gb", 0),)))
    with pytest.raises(ValueError, match="fit no legal"):
        MigLayout.adopt([SliceSpec("gpu0-a", 40 * GIB, n_chips=4),
                         SliceSpec("gpu0-b", 40 * GIB, n_chips=3)])


def test_adoption_rejects_an_overfull_gpu():
    specs = [SliceSpec("gpu0-a", 40 * GIB, n_chips=4),
             SliceSpec("gpu0-b", 40 * GIB, n_chips=4)]
    with pytest.raises(ValueError, match="fit no legal"):
        MigLayout.adopt(specs)


def test_split_merge_round_trips_rebuild_canonical_ids():
    layout = MigLayout.adopt([SliceSpec("gpu3-7g", 80 * GIB, n_chips=7)])
    split = Move("split", ("gpu3-7g",),
                 into=(("3g.40gb", 0), ("3g.40gb", 4)))
    layout.validate(split)
    halves = layout.apply(split)
    assert halves == ["gpu3-3g.40gb@0", "gpu3-3g.40gb@4"]
    merge = Move("merge", tuple(halves), into=(("7g.80gb", 0),))
    assert merge in layout.merges()
    layout.validate(merge)
    assert layout.apply(merge) == ["gpu3-7g.80gb@0"]
    again = Move("split", ("gpu3-7g.80gb@0",), into=split.into)
    assert layout.apply(again) == halves
    assert layout.apply(Move("merge", tuple(halves),
                             into=(("7g.80gb", 0),))) == ["gpu3-7g.80gb@0"]
    assert layout.is_legal()


def test_moves_are_legal_ordered_and_validated():
    layout = MigLayout.adopt(_dgx(2))
    merges = layout.merges()
    caps = [layout.products(m)[0][1] for m in merges]
    assert caps == sorted(caps, reverse=True)  # largest first
    assert merges[0] == Move("merge", tuple(sorted(
        s for s in layout.instances if s.startswith("gpu0-"))),
        into=(("7g.80gb", 0),))
    splits = layout.splits()
    assert splits[0].targets == ("gpu0-3g.40gb",)
    # finest first: three instances, the one with the most memory
    assert splits[0].into == (("1g.10gb", 4), ("1g.10gb", 5), ("1g.20gb", 6))
    for move in merges + splits:
        copy = MigLayout(instances=dict(layout.instances))
        copy.validate(move)
        copy.apply(move)
        assert copy.is_legal(), move
    for bad in [Move("merge", ("gpu0-2g.20gb", "gpu1-1g.10gb-a"),
                     into=(("4g.40gb", 0),)),
                Move("split", ("gpu0-3g.40gb",),
                     into=(("2g.20gb", 4), ("2g.20gb", 6))),
                Move("merge", ("gpu0-1g.10gb-a",), into=(("4g.40gb", 0),)),
                Move("gate", ("gpu0-3g.40gb",))]:
        with pytest.raises(ValueError):
            layout.validate(bad)


def test_buddy_layout_speaks_the_same_protocol():
    specs = [SliceSpec(f"f{k}", 5 * GIB, n_chips=1) for k in range(4)]
    coord = RepartitionCoordinator(JasdaScheduler(specs),
                                   FragmentationAware())
    layout = coord.state
    assert layout.name == "buddy" and layout.is_legal()
    assert layout.capacities() == (5 * GIB, 10 * GIB, 20 * GIB)
    merge = layout.merges()[0]
    assert layout.products(merge) == [(2, 10 * GIB)]
    layout.validate(merge)
    (parent,) = layout.apply(merge)
    assert layout.spec_for(parent).capacity_bytes == 10 * GIB
    assert layout.is_legal() and (parent, 0, 2) in layout.snapshot()
    assert layout.splits()[0] == Move("split", (parent,))
    layout.intervals["stray"] = (1, 1)  # overlaps the merged pair
    assert not layout.is_legal()


# ---------------------------------------------------------------------------
# the policy over the layout
# ---------------------------------------------------------------------------

def test_layout_is_picked_by_the_policy_field():
    with pytest.raises(ValueError, match="a100-80gb"):
        FragmentationAware(layout="h100")
    with pytest.raises(ValueError, match="power table"):
        EnergyAware(layout="a100-80gb")
    sched = JasdaScheduler(_dgx(1))
    coord = RepartitionCoordinator(
        sched, FragmentationAware(layout="a100-80gb"))
    assert isinstance(coord.state, MigLayout) and coord.lattice is None
    assert coord.layout_log[0].move is None
    assert coord.layout_log[0].layout == coord.state.snapshot()


def _agent(steady_gib, job="J0", work=40.0):
    from repro.core.jobs import JobAgent
    from repro.core.trp import fmp_standard
    from repro.core.types import JobSpec

    steady = steady_gib * GIB
    fmp = fmp_standard(0.3 * steady, steady, 0.1 * steady, rel_sigma=0.03)
    return JobAgent(JobSpec(job_id=job, arrival_time=0.0, total_work=work,
                            fmp=fmp))


def test_theta_floor_strands_a_job_no_instance_holds():
    """min_capacity 0, 60 GiB steady memory: only a 7g.80gb is θ-safe, so
    on a node without one the job's work is stranded and the coordinator
    reshapes a whole idle GPU into 7g.80gb."""
    sched = JasdaScheduler(_dgx(2))
    coord = RepartitionCoordinator(
        sched, FragmentationAware(layout="a100-80gb"))
    agent = _agent(60.0)
    assert agent.spec.min_capacity == 0.0
    sched.add_job(agent, 0.0)
    ctx = coord._context(0.0, frozenset())
    assert ctx.demand == ((40.0, 80 * GIB),)
    assert ctx.fragmentation == 1.0
    (move,) = coord.tick(0.0)
    assert move.kind == "merge" and move.into == (("7g.80gb", 0),)
    assert coord.frag_trace[-1] == (0.0, 1.0)
    assert "gpu0-7g.80gb@0" in sched.slices
    assert coord.state.is_legal() and _inventory_matches(coord)
    coord.tick(1.0)
    assert coord.frag_trace[-1] == (1.0, 0.0)


def test_theta_floor_leaves_small_jobs_at_min_capacity():
    sched = JasdaScheduler(_dgx(1))
    coord = RepartitionCoordinator(
        sched, FragmentationAware(layout="a100-80gb"))
    sched.add_job(_agent(4.0, "small"), 0.0)
    sched.add_job(_agent(30.0, "mid"), 0.0)
    ctx = coord._context(0.0, frozenset())
    assert dict(zip(["mid", "small"], (f for _, f in ctx.demand))) == {
        "mid": 40 * GIB, "small": 0.0}


@pytest.mark.parametrize("seed", range(4))
def test_random_demand_keeps_every_layout_legal(seed):
    """Seeded demand over the whole profile ladder: every move the policy
    makes leaves a legal layout whose instances are the scheduler's live
    slices, and once the node holds every floor nothing stays stranded."""
    agents = make_workload(24, seed=seed, arrival_rate=0.5,
                           work_range=(4.0, 30.0), mem_range_gb=(2.0, 50.0))
    r = simulate(JasdaScheduler(_dgx(2)), agents,
                 SimConfig(t_end=160.0, seed=seed,
                           repartition=FragmentationAware(layout="a100-80gb"),
                           migration=MigrationConfig()))
    coord = r.repartition
    assert coord.n_merges > 0
    for rec in coord.layout_log:
        gpus = {g for _, g, _, _ in rec.layout}
        for g in gpus:
            assert mig_legal([(n, s) for _, h, n, s in rec.layout if h == g])
        held = {(sid, next(p.compute for p in A100_80GB if p.name == n),
                 next(p.capacity_bytes for p in A100_80GB if p.name == n))
                for sid, _, n, _ in rec.layout}
        assert held == set(rec.live)
    frags = [f for _, f in coord.frag_trace]
    assert max(frags) > 0.0 and frags[-1] == 0.0


class _MergeGpu0(RepartitionPolicy):
    """Test policy: reshape all of GPU 0 into one 7g.80gb, once."""

    name = "merge-gpu0"
    layout = "a100-80gb"

    def __init__(self):
        self.done = False

    def propose(self, ctx):
        if self.done:
            return []
        self.done = True
        return [m for m in ctx.state.merges(ctx.specs)
                if m.into == (("7g.80gb", 0),)
                and m.targets[0].startswith("gpu0-")]


def test_forced_drain_migrates_onto_a_live_instance():
    """A commitment on a merged-away 1g instance, past its drain grace,
    migrates to a live instance with room (the reshaped 7g.80gb or the
    other GPU), never onto another instance of the same reshape."""
    sched = JasdaScheduler(_dgx(2))
    for a in make_workload(16, seed=5, work_range=(30.0, 60.0),
                           mem_range_gb=(1.0, 4.0)):
        sched.add_job(a, 0.0)
    for k in range(3):
        sched.run_round(float(k))
    # a chunk booked ahead (one already running cannot move: its own
    # interval overlaps every placement from now)
    doomed = [c.variant for c in sched.commitments
              if c.variant.slice_id == "gpu0-1g.10gb-a"
              and c.variant.t_start > 3.0]
    assert doomed
    planner = MigrationPlanner(sched, MigrationConfig(migration_budget=8))
    coord = RepartitionCoordinator(sched, _MergeGpu0(), drain_grace=0,
                                   migration=planner)
    (move,) = coord.tick(3.0)
    assert coord.n_forced == len(move.targets) and planner.n_migrated > 0
    moved = {c.variant.variant_id: c.variant.slice_id
             for c in sched.commitments}
    for v in doomed:
        sid = moved[v.variant_id + "~mig"]
        assert sid in sched.slices and sid not in move.targets
    assert coord.state.is_legal() and _inventory_matches(coord)
