"""The generator's world for a configuration with multi-node tasks.

`generate.world` knows single-node classes over a cluster.  A configuration
that also has `groups` (the workers came in allocations, and a multi-node
task runs only inside one) and `gangs` (ready multi-node tasks of some sizes)
gets the same world by the same rules, from the same helpers, with three more
arrays: the group of every worker, and the node count and the user priority
of every ready gang in queue order.  Plain data only; nothing of the program.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench import generate


@dataclass
class GangWorld(generate.World):
    worker_group: np.ndarray   # (W,) int64 allocation group of worker w
    gang_nodes: np.ndarray     # (G,) int64 nodes of ready gang g, queue order
    gang_prio: int             # the user priority every gang is submitted at


def world(config: dict, traffic: dict, seed: int,
          scale: dict | None = None) -> GangWorld:
    """As `generate.world`: every seed gets the same sizes in another order.
    Worker i lies in group i // group size, after the seed has paired the
    workers' sizes up; the gangs' sizes are split by the configuration's
    weights exactly and the seed orders them.  `scale` (tests and rehearsals
    only) overrides `workers`, `ready_tasks`, `groups` and `ready_gangs`."""
    scale = scale or {}
    base = generate.world(config, traffic, seed, scale)
    n_w = base.worker_total.shape[0]
    n_groups = int(scale.get("groups", config["groups"]["count"]))
    per_group = -(-n_w // n_groups)
    spec = config["gangs"]
    n_gangs = int(scale.get("ready_gangs", traffic["ready_gangs"]))
    sizes = np.asarray(spec["nodes"], dtype=np.int64)
    sizes = sizes[sizes <= per_group]
    weights = np.asarray(spec["weights"], dtype=np.float64)[: len(sizes)]
    nodes = np.repeat(sizes, generate._apportion(n_gangs, weights))
    return GangWorld(
        **vars(base),
        worker_group=np.arange(n_w, dtype=np.int64) // per_group,
        gang_nodes=generate._rng(seed, 4).permutation(nodes),
        gang_prio=int(spec["user_priority"]),
    )


def shape_signature(w: GangWorld) -> tuple:
    """What must not depend on the seed."""
    return generate.shape_signature(w) + (
        tuple(np.bincount(w.worker_group).tolist()),
        tuple(np.sort(w.gang_nodes).tolist()), w.gang_prio,
    )
