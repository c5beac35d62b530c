"""The ``sequential`` generator: a job sequence from a mix's parameter
file and ``--seed``, handed to the service in a closed loop.

A mix file (``chipbench/traffic/<mix>.json``) holds:

* ``apps``, ``input_sizes``: the workload families, every (app, input
  size) pair;
* ``n_jobs``: the sequence's length, the same for every seed.

The sequence is made of blocks, each holding every family once, in an
order the seed shuffles: any stretch of it asks for the same work
whatever the seed. The jobs run one at a time, as an application runs
alone on its node: each job arrives the instant the one before it
finishes, so each reaction of the service takes in one completion and
plans one arrival. No job has a deadline.
"""

from __future__ import annotations

import math
from typing import List

import numpy as np

WARM_PER_FAMILY = 2  # set-up replays this many jobs of each family


class Sequential:
    """The job sequence of one run, and the closed loop that hands it to
    the service."""

    def __init__(self, mix: dict, seed: int):
        pairs = [(a, float(s)) for s in mix["input_sizes"] for a in mix["apps"]]
        n = int(mix["n_jobs"])
        rng = np.random.default_rng(seed)
        order = np.concatenate(
            [rng.permutation(len(pairs)) for _ in range(-(-n // len(pairs)))]
        )[:n]
        self.n_families = len(pairs)
        self.specs = [pairs[k] for k in order]
        self.submitted: List[int] = []  # job ids handed to the service
        # set-up: each family characterized on its first arrival, and every
        # program of the window compiled, before the window opens
        self.warm_reactions = WARM_PER_FAMILY * self.n_families

    def intake(self, sched, now_s: float) -> list:
        """The jobs to submit after a commit at sim time ``now_s``: the next
        one once the one before it has started, arriving when that one
        finishes (at ``now_s`` when nothing runs)."""
        from repro.fleet.scheduler import Job

        i = len(self.submitted)
        in_flight = i - len(sched.completed)
        if i >= len(self.specs) or in_flight > 1:
            return []
        if in_flight == 1:
            if not sched._finish_queue:
                return []  # the one before it has not started yet
            t = sched._finish_queue[-1].finish_s
        else:
            t = now_s
        app, size = self.specs[i]
        self.submitted.append(i)
        return [Job(job_id=i, app=app, input_size=size, deadline_s=math.inf, arrival_s=t)]

    def failed(self, reaction) -> bool:
        """Every reaction takes in a completion and launches the next job:
        one that launched none failed."""
        return reaction.placed == 0


def make(mix: dict, seed: int) -> Sequential:
    return Sequential(mix, seed)
