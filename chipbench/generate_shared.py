"""The generator's world for a configuration whose allocations each have
one node shape (`shared-1k`).

`generate_gang.world` lets the seed pair the workers' sizes up and number
the classes, so every seed is another cluster and another packing.  Here
nothing of the cluster or the backlog depends on the seed: the
configuration's `groups.rule` gives every worker of group g the node shape
(cpus[g mod 3], gpus[g mod 5], mem[(g + g div 3) mod 3]) of the lists under
`workers`, as one allocation from one Slurm partition has one shape; the
classes keep the order `generate._classes` gives them, and the ready tasks
are numbered level by level.  The seed orders the gangs (as
`generate_gang.world` does), and the drivers' churn picks which tasks and
gangs end, nothing else.  Plain data only; nothing of the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import generate, generate_gang


def group_shapes(config: dict, n_groups: int) -> np.ndarray:
    """(G, R) int64 fractions: the node shape of every group, by the rule."""
    wspec = config["workers"]
    shapes = np.zeros((n_groups, len(config["resources"])), dtype=np.int64)
    for g in range(n_groups):
        pick = {"cpus": g % 3, "gpus": g % 5, "mem": (g + g // 3) % 3}
        for r, name in enumerate(config["resources"]):
            sizes = wspec[name]
            shapes[g, r] = sizes[pick[name] % len(sizes)] * generate.UNIT
    return shapes


def world(config: dict, traffic: dict, seed: int,
          scale: dict | None = None) -> generate_gang.GangWorld:
    """`generate_gang.world` with the workers' sizes set by the group rule
    and the classes and tasks in a fixed order.  `scale` (tests and
    rehearsals only) overrides `workers`, `ready_tasks`, `groups` and
    `ready_gangs`."""
    scale = scale or {}
    base = generate_gang.world(config, traffic, seed, scale)
    n_groups = int(base.worker_group.max()) + 1
    total = group_shapes(config, n_groups)[base.worker_group]
    slots = np.array([generate.task_max_count(row) for row in total],
                     dtype=np.int64)
    needs, n_variants, weights = generate._classes(
        config["classes"], list(config["resources"]))
    n_p = int(config["priority_levels"])
    n_tasks = int(scale.get("ready_tasks", traffic["ready_tasks"]))
    per_level = generate._apportion(n_tasks, np.repeat(weights, n_p))
    levels = np.repeat(np.arange(len(per_level)), per_level)
    return dataclasses.replace(
        base, worker_total=total, worker_slots=slots, class_needs=needs,
        class_variants=n_variants,
        task_class=(levels // n_p).astype(np.int32),
        task_prio=(levels % n_p).astype(np.int32),
    )


shape_signature = generate_gang.shape_signature
