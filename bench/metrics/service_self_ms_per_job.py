"""The campaign service's own host time per job, from its spans:
``job_open``, ``prep_topo`` and ``cell_save``, summed over the window
and averaged over its jobs."""

SERVICE = ("job_open", "prep_topo", "cell_save")


def read(run):
    jobs = [j for j in run.jobs if "cells_wall_s" in j]
    ms = [s["dur"] / 1e3 for s in run.spans if s["name"] in SERVICE]
    if not jobs or not ms:
        return None
    return sum(ms) / len(jobs)
