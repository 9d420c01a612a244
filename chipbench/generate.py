"""The one general generator: a cluster and its ready backlog from `--seed`.

Plain data only (numpy arrays and lists), built from a configuration file and
a traffic file.  The drivers hand this data to the program; the plain
references work on the same data and never see what the program made of it.
`--seed` changes which workers, classes and tasks there are, never how many:
every size below comes from the files.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# resource amounts are fixed-point fractions, 10 000 to the unit (upstream
# HyperQueue's FRACTIONS_PER_UNIT; the program's resources/amount.py agrees
# and the tick driver checks that it still does)
UNIT = 10_000


@dataclass
class World:
    resources: list            # names, in resource-id order
    worker_total: np.ndarray   # (W, R) int64 fractions
    worker_slots: np.ndarray   # (W,) int64 simultaneous-task bound
    class_needs: np.ndarray    # (C, V, R) int64 fractions, all-zero = absent
    class_variants: np.ndarray  # (C,) int64 number of variants
    n_priorities: int
    task_class: np.ndarray     # (N,) int32 class of ready task t
    task_prio: np.ndarray      # (N,) int32 user priority of ready task t


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def task_max_count(total_row) -> int:
    """Upstream's bound on simultaneously running single-node tasks: the sum
    of the pool sizes in whole units (each running task holds at least one
    unit of some pool), at least 1, capped."""
    return int(min(512, max(sum(int(a) // UNIT for a in total_row if a > 0), 1)))


def _apportion(n: int, weights) -> np.ndarray:
    """n split in proportion to `weights`, exactly (largest remainders)."""
    w = np.asarray(weights, dtype=np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[: n - int(counts.sum())]] += 1
    return counts


def _classes(spec: dict, resources: list):
    """Every combination of the class mix's choices but those left out, in
    one fixed order, each with the share of tasks the mix gives it.  Returns
    (needs (C, V, R), variants (C,), weights (C,))."""
    r_cpus = resources.index("cpus")
    r_gpus = resources.index("gpus") if "gpus" in resources else None
    r_mem = resources.index("mem") if "mem" in resources else None
    gpus = spec.get("gpu_amounts", [0]) if r_gpus is not None else [0]
    gpu_w = spec.get("gpu_weights", [1.0] * len(gpus))
    mems = spec.get("mem", [0]) if r_mem is not None else [0]
    fallbacks = spec.get("fallback", [False])
    left_out = [
        (float(x.get("gpu", -1)), int(x.get("mem", -1)))
        for x in spec.get("left_out", [])
    ]
    rows = []
    for n_cpus in spec["cpus"]:
        for gpu, gw in zip(gpus, gpu_w):
            for mem in mems:
                if (float(gpu), int(mem)) in left_out:
                    continue
                for fallback in fallbacks:
                    rows.append((int(n_cpus), float(gpu), int(mem),
                                 bool(fallback), float(gw)))
    two = any(r[3] for r in rows)
    needs = np.zeros((len(rows), 2 if two else 1, len(resources)),
                     dtype=np.int64)
    n_variants = np.ones(len(rows), dtype=np.int64)
    for c, (n_cpus, gpu, mem, fallback, _w) in enumerate(rows):
        needs[c, 0, r_cpus] = n_cpus * UNIT
        if gpu:
            needs[c, 0, r_gpus] = int(round(gpu * UNIT))
        if r_mem is not None:
            needs[c, 0, r_mem] = mem * UNIT
        if fallback:
            factor = int(spec.get("fallback_cpu_factor", 2))
            needs[c, 1, r_cpus] = factor * n_cpus * UNIT
            if r_mem is not None:
                needs[c, 1, r_mem] = mem * UNIT
            n_variants[c] = 2
    return needs, n_variants, np.asarray([r[4] for r in rows])


def world(config: dict, traffic: dict, seed: int, scale: dict | None = None) -> World:
    """Every seed gets the same set of sizes in another order: the same
    number of workers of each size of each resource (the seed pairs them
    up), the same classes (the seed numbers them), the same number of ready
    tasks at every (class, priority) level (the seed orders them).
    `scale` (tests and the selfcheck only) overrides `workers` and
    `ready_tasks` so that a CPU rehearsal fits in seconds."""
    scale = scale or {}
    resources = list(config["resources"])
    wspec = config["workers"]
    n_w = int(scale.get("workers", wspec["count"]))
    n_tasks = int(scale.get("ready_tasks", traffic["ready_tasks"]))
    rng = _rng(seed, 1)
    total = np.zeros((n_w, len(resources)), dtype=np.int64)
    for r, name in enumerate(resources):
        sizes = np.asarray(wspec[name], dtype=np.int64)
        column = np.repeat(sizes, _apportion(n_w, np.ones(len(sizes))))
        total[:, r] = rng.permutation(column) * UNIT
    slots = np.array([task_max_count(row) for row in total], dtype=np.int64)
    needs, n_variants, weights = _classes(config["classes"], resources)
    n_c = needs.shape[0]
    order = _rng(seed, 2).permutation(n_c)
    needs, n_variants, weights = needs[order], n_variants[order], weights[order]
    n_p = int(config["priority_levels"])
    per_level = _apportion(n_tasks, np.repeat(weights, n_p))
    if n_tasks >= n_c * n_p and per_level.min() < 1:
        raise ValueError("a (class, priority) level would hold no task")
    levels = _rng(seed, 3).permutation(np.repeat(np.arange(n_c * n_p), per_level))
    return World(
        resources=resources, worker_total=total, worker_slots=slots,
        class_needs=needs, class_variants=n_variants, n_priorities=n_p,
        task_class=(levels // n_p).astype(np.int32),
        task_prio=(levels % n_p).astype(np.int32),
    )


def shape_signature(w: World) -> tuple:
    """What must not depend on the seed."""
    return (
        w.worker_total.shape, w.class_needs.shape, w.n_priorities,
        len(w.task_class),
        tuple(np.sort(w.worker_total, axis=0).ravel().tolist()),
        tuple(sorted(np.bincount(
            w.task_class.astype(np.int64) * w.n_priorities + w.task_prio
        ).tolist())),
    )
