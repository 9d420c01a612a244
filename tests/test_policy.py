"""Policy-brain unit tests (scheduler/policy.py + scheduler/predict.py):
table parsing and validation, task-class labelling, per-tick affinity-row
resolution, fairness/prediction priority boosts, the starvation-aware Jain
fold, and the runtime-prediction EWMA with its offline journal seed.
"""

import types

import numpy as np
import pytest

from hyperqueue_tpu.resources.map import ResourceIdMap, ResourceRqMap
from hyperqueue_tpu.resources.request import (
    ResourceRequest,
    ResourceRequestEntry,
    ResourceRequestVariants,
)
from hyperqueue_tpu.scheduler.policy import (
    PolicyState,
    PolicyTable,
    TickPolicyContext,
    build_policy,
    task_class,
)
from hyperqueue_tpu.scheduler.predict import RuntimePredictor
from hyperqueue_tpu.scheduler.queues import (
    BLEVEL_STRIDE,
    decode_sched_blevel,
    decode_sched_job,
    encode_sched_priority,
)
from hyperqueue_tpu.scheduler.tick import Batch

pytestmark = pytest.mark.policy

U = 10_000


# -- scaffolding -----------------------------------------------------------

def make_maps(names=("cpus",)):
    resource_map = ResourceIdMap()
    for n in names:
        resource_map.get_or_create(n)
    return resource_map, ResourceRqMap()


def rq_for(resource_map, rq_map, *entries):
    """rq id for a single-variant request over (name, amount) entries."""
    req = ResourceRequest(entries=tuple(
        ResourceRequestEntry(resource_map.get_or_create(n), amt * U)
        for n, amt in entries
    ))
    return rq_map.get_or_create(ResourceRequestVariants.single(req))


def batch(rq_id, job_id, size=4, user_prio=0):
    return Batch(
        rq_id=rq_id,
        priority=(user_prio, encode_sched_priority(job_id)),
        size=size,
    )


def fake_workers(groups):
    """worker_id -> worker with .group, ids 1..n in the given order."""
    return {
        i + 1: types.SimpleNamespace(group=g) for i, g in enumerate(groups)
    }


def fake_ledger(rows=None, open_runs=None):
    return types.SimpleNamespace(rows=rows or {}, open_runs=open_runs or {})


def policy_toml(tmp_path, text):
    p = tmp_path / "policy.toml"
    p.write_text(text)
    return str(p)


# -- PolicyTable parsing ---------------------------------------------------

def test_from_file_parses_all_tables(tmp_path):
    path = policy_toml(tmp_path, """
[affinity."cpus"]
"*" = 1.0
fast = 2.5
slow = 0.0

[fairness]
enabled = true
max_boost = 6

[prediction]
enabled = true
max_boost = 3
ewma_alpha = 0.5
seed_journal = "/tmp/does-not-exist.journal"
""")
    t = PolicyTable.from_file(path)
    assert t.source == path
    assert t.affinity == {"cpus": {"*": 1.0, "fast": 2.5, "slow": 0.0}}
    assert t.fairness_enabled and t.fairness_max_boost == 6
    assert t.prediction_enabled and t.prediction_max_boost == 3
    assert t.ewma_alpha == 0.5
    assert t.seed_journal == "/tmp/does-not-exist.journal"


def test_from_file_defaults(tmp_path):
    t = PolicyTable.from_file(policy_toml(tmp_path, "[fairness]\n"))
    assert t.affinity == {}
    assert not t.fairness_enabled and not t.prediction_enabled
    assert t.fairness_max_boost == 4 and t.prediction_max_boost == 4


def test_from_file_rejects_non_table_affinity_row(tmp_path):
    path = policy_toml(tmp_path, "[affinity]\ncpus = 2.0\n")
    with pytest.raises(ValueError, match="must be a"):
        PolicyTable.from_file(path)


def test_from_file_rejects_negative_weight(tmp_path):
    path = policy_toml(tmp_path, '[affinity."cpus"]\nfast = -1.0\n')
    with pytest.raises(ValueError, match="negative"):
        PolicyTable.from_file(path)


def test_weight_fallback_chain():
    t = PolicyTable(affinity={
        "cpus": {"fast": 2.0, "*": 0.5},
        "*": {"fast": 3.0},
    })
    # exact row, exact class
    assert t.weight("cpus", "fast") == 2.0
    # exact row, wildcard class
    assert t.weight("cpus", "slow") == 0.5
    # wildcard row, exact class
    assert t.weight("gpus", "fast") == 3.0
    # wildcard row, missing class -> implicit 1.0
    assert t.weight("gpus", "slow") == 1.0
    assert t.has_row("cpus") and t.has_row("anything")
    # no wildcard row at all -> unknown classes have no row
    flat = PolicyTable(affinity={"cpus": {"fast": 2.0}})
    assert not flat.has_row("gpus")
    assert flat.weight("gpus", "fast") == 1.0


# -- task_class ------------------------------------------------------------

def test_task_class_labels():
    resource_map, rq_map = make_maps(("cpus", "gpus"))
    rq = rq_for(resource_map, rq_map, ("gpus", 1), ("cpus", 2))
    # sorted "+"-joined names of the first variant
    assert task_class(rq_map.get_variants(rq), resource_map) == "cpus+gpus"
    nodes = ResourceRequestVariants.single(ResourceRequest(n_nodes=2))
    assert task_class(nodes, resource_map) == "nodes"
    empty = types.SimpleNamespace(variants=[
        types.SimpleNamespace(n_nodes=0, entries=()),
    ])
    assert task_class(empty, resource_map) == "none"


# -- tick_context ----------------------------------------------------------

def test_tick_context_rows_align_to_worker_order():
    resource_map, rq_map = make_maps()
    rq = rq_for(resource_map, rq_map, ("cpus", 1))
    table = PolicyTable(affinity={"cpus": {"fast": 2.0, "*": 1.0}})
    state = PolicyState(table)
    workers = fake_workers(["fast", "", "slow"])  # "" -> "default"
    batches = [batch(rq, job_id=1)]
    ctx = state.tick_context(
        workers, rq_map, resource_map, [2, 1, 3], batches)
    assert ctx is not None and bool(ctx)
    row = ctx.affinity_for(rq)
    assert row.dtype == np.float32
    # aligned to worker_ids [2, 1, 3] = default, fast, slow
    assert row.tolist() == [1.0, 2.0, 1.0]
    assert ctx.boosts == {} and ctx.boost_for(1) == 0


def test_tick_context_drops_uniform_positive_row():
    resource_map, rq_map = make_maps()
    rq = rq_for(resource_map, rq_map, ("cpus", 1))
    table = PolicyTable(affinity={"cpus": {"*": 1.5}})
    state = PolicyState(table)
    ctx = state.tick_context(
        fake_workers(["a", "b"]), rq_map, resource_map, [1, 2],
        [batch(rq, job_id=1)],
    )
    # a uniform positive row cannot reorder or exclude -> flat fast path
    assert ctx is None


def test_tick_context_keeps_uniform_zero_row():
    resource_map, rq_map = make_maps()
    rq = rq_for(resource_map, rq_map, ("cpus", 1))
    table = PolicyTable(affinity={"cpus": {"slow": 0.0, "*": 1.0}})
    state = PolicyState(table)
    ctx = state.tick_context(
        fake_workers(["slow", "fast"]), rq_map, resource_map, [1, 2],
        [batch(rq, job_id=1)],
    )
    # zero weight is a hard exclusion, so the row must survive
    assert ctx.affinity_for(rq).tolist() == [0.0, 1.0]


def test_tick_context_none_when_no_rows_and_no_boosts():
    resource_map, rq_map = make_maps()
    rq = rq_for(resource_map, rq_map, ("cpus", 1))
    state = PolicyState(PolicyTable())  # no affinity, nothing enabled
    ctx = state.tick_context(
        fake_workers(["a"]), rq_map, resource_map, [1],
        [batch(rq, job_id=1)],
    )
    assert ctx is None


# -- fairness + prediction boosts ------------------------------------------

def test_fairness_boost_favors_deficit_job():
    resource_map, rq_map = make_maps()
    rq = rq_for(resource_map, rq_map, ("cpus", 1))
    ledger = fake_ledger(rows={
        1: {"label": "hog", "resource_seconds": {"cpus": 10.0}},
        2: {"label": "starved", "resource_seconds": {}},
    })
    table = PolicyTable(fairness_enabled=True, fairness_max_boost=4)
    state = PolicyState(table, ledger=ledger)
    batches = [batch(rq, job_id=1), batch(rq, job_id=2)]
    ctx = state.tick_context(
        fake_workers(["a"]), rq_map, resource_map, [1], batches)
    # job 1 holds 100% of cpus-seconds (share 1.0 >= fair 0.5): no boost;
    # job 2 holds nothing (share 0): the full deficit boost
    assert ctx.boosts == {2: 4}
    assert state.last_boost_range == (4, 4)
    assert ctx.boost_for_sched(encode_sched_priority(2)) == 4
    assert ctx.boost_for_sched(encode_sched_priority(1)) == 0


def test_fairness_boost_needs_multiple_active_jobs():
    resource_map, rq_map = make_maps()
    rq = rq_for(resource_map, rq_map, ("cpus", 1))
    ledger = fake_ledger(rows={1: {"resource_seconds": {}}})
    state = PolicyState(
        PolicyTable(fairness_enabled=True, fairness_max_boost=4),
        ledger=ledger,
    )
    ctx = state.tick_context(
        fake_workers(["a"]), rq_map, resource_map, [1],
        [batch(rq, job_id=1)],
    )
    assert ctx is None
    assert state.last_boost_range == (0, 0)


def test_prediction_boost_is_lpt_proportional_and_sums_with_fairness():
    resource_map, rq_map = make_maps()
    rq = rq_for(resource_map, rq_map, ("cpus", 1))
    predictor = RuntimePredictor()
    predictor.observe("short", 10.0)
    predictor.observe("long", 40.0)
    names = {1: "long", 2: "short"}
    ledger = fake_ledger(rows={
        1: {"resource_seconds": {"cpus": 8.0}},
        2: {"resource_seconds": {}},
    })
    table = PolicyTable(
        fairness_enabled=True, fairness_max_boost=4,
        prediction_enabled=True, prediction_max_boost=4,
    )
    state = PolicyState(
        table, predictor=predictor, ledger=ledger, job_name=names.get)
    batches = [batch(rq, job_id=1), batch(rq, job_id=2)]
    ctx = state.tick_context(
        fake_workers(["a"]), rq_map, resource_map, [1], batches)
    # job 1: longest predicted class -> full LPT boost (no fairness boost);
    # job 2: fairness deficit 4 + LPT round(4 * 10/40) = 1
    assert ctx.boosts == {1: 4, 2: 5}
    assert state.last_boost_range == (4, 5)
    stats = state.stats()
    assert stats["boost_range"] == [4, 5]
    assert stats["prediction"]["observations"] == 2


# -- priority-encoding boost arithmetic ------------------------------------

def test_boost_stride_arithmetic_reorders_across_jobs():
    # a boost of k sorts a batch as if its job had been submitted k jobs
    # earlier, without disturbing the b-level component
    sched = encode_sched_priority(7, blevel=3)
    boosted = sched + 2 * BLEVEL_STRIDE
    assert decode_sched_job(sched) == 7
    assert decode_sched_job(boosted) == 5
    assert decode_sched_blevel(boosted) == decode_sched_blevel(sched) == 3
    # boosted job 7 now outranks unboosted job 6 (higher sched sorts first)
    assert boosted > encode_sched_priority(6, blevel=3)
    # ...but still loses to a job boosted further
    assert boosted < encode_sched_priority(6, blevel=3) + 3 * BLEVEL_STRIDE


# -- Jain fairness fold ----------------------------------------------------

def test_observe_jain_none_without_ledger_or_usage():
    assert PolicyState(PolicyTable()).observe_jain() is None
    state = PolicyState(PolicyTable(), ledger=fake_ledger())
    assert state.observe_jain() is None
    # open runs with zero usage don't count as running
    state = PolicyState(PolicyTable(), ledger=fake_ledger(
        open_runs={(1, 0): {"usage": {}}}))
    assert state.observe_jain() is None


def test_observe_jain_counts_starved_live_jobs():
    open_runs = {
        (1, 0): {"usage": {"cpus": 2.0}},
        (1, 1): {"usage": {"cpus": 2.0}},
    }
    # without live-job context a monopolized cluster looks perfectly fair
    state = PolicyState(PolicyTable(), ledger=fake_ledger(open_runs=open_runs))
    assert state.observe_jain() == pytest.approx(1.0)
    # with it, the starved-but-live job 2 drags the index to 0.5
    state = PolicyState(
        PolicyTable(), ledger=fake_ledger(open_runs=open_runs),
        live_jobs=lambda: [1, 2],
    )
    assert state.observe_jain() == pytest.approx(0.5)
    assert state.observe_jain() == pytest.approx(0.5)
    stats = state.stats()
    assert stats["jain"] == {"last": 0.5, "avg": 0.5, "ticks": 2}


def test_observe_jain_equal_split_scores_one():
    state = PolicyState(PolicyTable(), ledger=fake_ledger(open_runs={
        (1, 0): {"usage": {"cpus": 3.0}},
        (2, 0): {"usage": {"cpus": 3.0}},
    }), live_jobs=lambda: [1, 2])
    assert state.observe_jain() == pytest.approx(1.0)


# -- RuntimePredictor ------------------------------------------------------

def test_predictor_ewma_and_hit_rate():
    p = RuntimePredictor(alpha=0.5)
    assert p.predict("a") is None          # miss
    p.observe("a", 10.0)                   # first obs sets the EWMA directly
    assert p.peek("a") == 10.0
    p.observe("a", 20.0)
    assert p.peek("a") == pytest.approx(15.0)   # 10 + 0.5 * (20 - 10)
    p.observe("a", -1.0)                   # negative runtimes are ignored
    p.observe("", 5.0)                     # empty labels are ignored
    assert p.peek("a") == pytest.approx(15.0)
    assert p.predict("a") == pytest.approx(15.0)  # hit
    assert p.hit_rate() == pytest.approx(0.5)
    assert p.n_classes() == 1
    stats = p.stats()
    assert stats["observations"] == 2
    assert "seeded_from" not in stats      # peek never touches the counters


def test_predictor_seed_from_journal(tmp_path):
    from hyperqueue_tpu.events.journal import Journal

    path = tmp_path / "seed.journal"
    j = Journal(path)
    j.open_for_append()
    j.write({"event": "job-submitted", "job": 1, "time": 0.0,
             "desc": {"name": "train"}})
    # trace stamps preferred: runtime = exited_at - spawned_at = 7
    j.write({"event": "task-started", "job": 1, "task": 0,
             "started_at": 1.0})
    j.write({"event": "task-finished", "job": 1, "task": 0, "time": 9.5,
             "trace": {"spawned_at": 1.5, "exited_at": 8.5}})
    # no trace: runtime = commit time - started_at = 3
    j.write({"event": "task-started", "job": 1, "task": 1,
             "started_at": 10.0})
    j.write({"event": "task-finished", "job": 1, "task": 1, "time": 13.0})
    # unpaired finish (no start, no trace) is skipped, not fatal
    j.write({"event": "task-finished", "job": 1, "task": 2, "time": 14.0})
    j.flush()
    j.close()

    p = RuntimePredictor(alpha=0.5)
    assert p.seed_from_journal(str(path)) == 2
    assert p.seeded_from == str(path)
    assert p.seeded_samples == 2
    assert p.peek("train") == pytest.approx(7.0 + 0.5 * (3.0 - 7.0))


# -- build_policy ----------------------------------------------------------

def test_build_policy_none_without_file():
    assert build_policy(None) is None
    assert build_policy("") is None


def test_build_policy_wires_predictor_and_ledger(tmp_path):
    path = policy_toml(tmp_path, """
[prediction]
enabled = true
ewma_alpha = 0.25
""")
    ledger = fake_ledger()
    state = build_policy(path, ledger=ledger, live_jobs=lambda: [])
    assert isinstance(state, PolicyState)
    assert state.ledger is ledger
    assert state.predictor is not None
    assert state.predictor.alpha == 0.25
    assert state.table.source == path
    # TickPolicyContext truthiness contract
    assert not TickPolicyContext({}, {})
    assert TickPolicyContext({}, {1: 2})


# -- the policy against the flat objective, in the simulator ---------------

_AB = {
    # bursts of four tenants with unlike durations landing at once on a
    # saturated pool: fairness + prediction must win, and share more evenly
    "bursty-hetero": dict(
        workload=("bursty", dict(
            seed=11, n_tenants=4, bursts_per_tenant=2, tasks_per_burst=150,
            window=0.0, tenant_dur_scales=[0.25, 4.0, 1.0, 0.5])),
        workers=2, groups=1, seed=11, strict=True, jain=True,
        policy="[fairness]\nenabled = true\nmax_boost = 8\n"
               "[prediction]\nenabled = true\nmax_boost = 2\n"
               "ewma_alpha = 0.3\nseed_journal = \"{journal}\"\n",
    ),
    # long tasks as their own job: the LPT boost, its predictor seeded
    # from the flat run's journal, starts the tail first
    "straggler-tail": dict(
        workload=("tail", dict(seed=5, n_tasks=500, split_long=True)),
        workers=8, groups=1, seed=5, strict=True, jain=False,
        policy="[prediction]\nenabled = true\nmax_boost = 4\n"
               "ewma_alpha = 0.3\nseed_journal = \"{journal}\"\n",
    ),
    # a worker-group affinity matrix reorders the water-fill: never worse
    "stress-dag": dict(
        workload=("dag", dict(seed=9, layers=8, width=16)),
        workers=8, groups=2, seed=9, strict=False, jain=False,
        policy="[affinity.\"cpus\"]\n\"g0\" = 2.0\n\"*\" = 1.0\n",
    ),
}


@pytest.mark.sim
@pytest.mark.parametrize("label", sorted(_AB))
def test_weighted_policy_against_flat_objective_in_sim(label, tmp_path):
    """One seeded workload under `greedy-fused`, flat objective against
    the policy file: makespan in virtual time never worse (strictly
    better where the policy has something to exploit), the time-averaged
    Jain index up where fairness is on, and a journal-seeded predictor
    that has observed runtimes."""
    from hyperqueue_tpu.sim import build, run_scenario

    spec = _AB[label]
    name, kwargs = spec["workload"]

    def run(policy_file, server_dir=None):
        return run_scenario(
            build(name, **kwargs), seed=spec["seed"],
            n_workers=spec["workers"], worker_groups=spec["groups"],
            scheduler="greedy-fused", server_dir=server_dir,
            server_kwargs={"policy_file": policy_file},
        )

    flat_dir = tmp_path / "flat"
    flat_dir.mkdir()
    flat_toml = tmp_path / "flat.toml"
    flat_toml.write_text("[fairness]\nenabled = false\n")
    flat = run(str(flat_toml), server_dir=flat_dir)
    weighted = run(policy_toml(
        tmp_path, spec["policy"].format(journal=flat_dir / "journal.bin")
    ))
    for res in (flat, weighted):
        assert not res.violations
        assert res.audit["finished"] == res.n_tasks
    if spec["strict"]:
        assert weighted.makespan < flat.makespan - 1e-6
    else:
        assert weighted.makespan <= flat.makespan + 1e-6
    if spec["jain"]:
        assert weighted.policy["jain"]["avg"] > flat.policy["jain"]["avg"]
    if "seed_journal" in spec["policy"]:
        assert weighted.policy["prediction"]["observations"] > 0
