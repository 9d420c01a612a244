"""Plain accounting of the served cell's guarantee: every acknowledged task
runs exactly once.

Three records are read once the server has stopped, none of them through the
scheduler: what the client was acknowledged (the harness counted it), the
journal as it stands on disk, and the workers' own record of what they
started and finished.  Nothing is expected to be terminal: the window stops
a run that would take many minutes to drain.
"""

from __future__ import annotations

from collections import Counter


def audit(acked_tasks: int, journal_records, worker_started, worker_finished,
          client_counters: dict | None = None) -> dict:
    """The numbers compared, each of which has to be 0.

    journal_records: dicts with `event`, `job`, `task`, `n_tasks`.
    worker_started / worker_finished: (task id, instance) pairs, task id
    being job << 32 | task.
    client_counters: the job's task counts by state as the client reads
    them before the stop, with `n_tasks`."""
    submitted = 0
    started: Counter = Counter()
    finished: Counter = Counter()
    other_terminal = 0
    for record in journal_records:
        kind = record.get("event")
        if kind == "job-submitted":
            submitted += int(record.get("n_tasks", 0))
        elif kind in ("task-started", "task-finished", "task-failed",
                      "task-canceled"):
            tid = (int(record["job"]) << 32) | int(record["task"])
            if kind == "task-started":
                started[tid] += 1
            elif kind == "task-finished":
                finished[tid] += 1
            else:
                other_terminal += 1
    ran = Counter(task for task, _instance in worker_started)
    ran_to_end = {task for task, _instance in worker_finished}
    numbers = {
        # acknowledged to the client but not in the journal
        "acked_not_durable": max(0, acked_tasks - submitted),
        "finished_twice": sum(1 for n in finished.values() if n > 1),
        "started_twice": sum(1 for n in started.values() if n > 1),
        "finished_unstarted": sum(1 for t in finished if t not in started),
        # the journal says finished, no worker ran it to its end
        "finished_never_ran": sum(1 for t in finished if t not in ran_to_end),
        # the journal says started, no worker ever started it
        "started_never_ran": sum(1 for t in started if t not in ran),
        "ran_twice": sum(1 for n in ran.values() if n > 1),
        "failed_or_canceled": other_terminal,
    }
    if client_counters is not None:
        states = sum(v for k, v in client_counters.items() if k != "n_tasks")
        numbers["client_unaccounted"] = abs(
            int(client_counters["n_tasks"]) - states
        ) + abs(int(client_counters["n_tasks"]) - acked_tasks)
    counts = {
        "journal_submitted": submitted,
        "journal_started": len(started),
        "journal_finished": len(finished),
        "workers_started": len(ran),
        "workers_finished": len(ran_to_end),
    }
    return {"numbers": numbers, "counts": counts}
