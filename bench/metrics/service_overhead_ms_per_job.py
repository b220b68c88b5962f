"""Campaign service overhead per job: the job's host wall less the wall
its cells report (plan-cache reads, checkpoints, CSV and metrics
streams), averaged over the window's jobs."""


def read(run):
    jobs = [j for j in run.jobs if "cells_wall_s" in j]
    if not jobs:
        return None
    return 1e3 * sum(j["wall_s"] - sum(j["cells_wall_s"])
                     for j in jobs) / len(jobs)
