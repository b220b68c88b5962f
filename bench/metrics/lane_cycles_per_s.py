"""Simulated lanes × cycles completed over the whole window's host time,
through the campaign service."""


def read(run):
    if not run.jobs or "cells_wall_s" not in run.jobs[0]:
        return None
    return sum(j["lanes"] * j["cycles"] for j in run.jobs) / run.window_s
