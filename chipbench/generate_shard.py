"""The generator's world for a configuration with whole-node classes.

`generate.world` knows classes that ask for amounts.  A configuration that
also has `whole_node_classes` (requests with `cpus = all`, upstream's policy
`all`) gets the same world by the same rules, from the same helpers, with
one more array: `class_all` marks, per (class, variant, resource), a request
for the worker's whole pool of that resource.  Such an entry has no amount,
so `class_needs` is 0 there.  Plain data only; nothing of the program.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench import generate


@dataclass
class ShardWorld(generate.World):
    class_all: np.ndarray      # (C, V, R) bool, the whole pool is asked for


def _all_classes(config: dict, resources: list):
    """The plain classes and then the whole-node classes, one table:
    (needs (C, V, R), variants (C,), weights (C,), whole (C, V, R) bool)."""
    needs, n_variants, weights = generate._classes(config["classes"], resources)
    spec = dict(config["whole_node_classes"])
    if spec.pop("cpus") != "all":
        raise ValueError("whole_node_classes asks for cpus = all")
    w_needs, w_variants, w_weights = generate._classes(
        {**spec, "cpus": [0]}, resources)
    n_v = max(needs.shape[1], w_needs.shape[1])
    pad = lambda a: np.pad(  # noqa: E731
        a, ((0, 0), (0, n_v - a.shape[1]), (0, 0)))
    whole = np.zeros((len(needs) + len(w_needs), n_v, len(resources)),
                     dtype=bool)
    whole[len(needs):, 0, resources.index("cpus")] = True
    return (np.concatenate([pad(needs), pad(w_needs)]),
            np.concatenate([n_variants, w_variants]),
            np.concatenate([weights, w_weights]), whole)


def world(config: dict, traffic: dict, seed: int,
          scale: dict | None = None) -> ShardWorld:
    """As `generate.world`: every seed gets the same sizes in another order.
    `scale` (tests and rehearsals only) overrides `workers` and
    `ready_tasks`."""
    scale = scale or {}
    resources = list(config["resources"])
    n_tasks = int(scale.get("ready_tasks", traffic["ready_tasks"]))
    # the workers are the generator's own, drawn as for any configuration
    cluster = generate.world(config, traffic, seed, {**scale, "ready_tasks": 0})
    needs, n_variants, weights, whole = _all_classes(config, resources)
    n_c = needs.shape[0]
    order = generate._rng(seed, 2).permutation(n_c)
    needs, n_variants, weights, whole = (
        needs[order], n_variants[order], weights[order], whole[order])
    n_p = int(config["priority_levels"])
    per_level = generate._apportion(n_tasks, np.repeat(weights, n_p))
    if n_tasks >= n_c * n_p and per_level.min() < 1:
        raise ValueError("a (class, priority) level would hold no task")
    levels = generate._rng(seed, 3).permutation(
        np.repeat(np.arange(n_c * n_p), per_level))
    return ShardWorld(
        resources=resources, worker_total=cluster.worker_total,
        worker_slots=cluster.worker_slots,
        class_needs=needs, class_variants=n_variants, n_priorities=n_p,
        task_class=(levels // n_p).astype(np.int32),
        task_prio=(levels % n_p).astype(np.int32),
        class_all=whole,
    )
