"""Device time of the simulator's runner programs in the traced window,
summed over the chips, per simulated lane-cycle."""

RUNNER = "jit_run"


def read(run):
    if run.trace is None:
        return None
    lane_cycles = sum(j["lanes"] * j["cycles"] for j in run.jobs)
    dev_s = sum(v for k, v in run.trace["modules"].items()
                if k.startswith(RUNNER)) * run.trace["devices"]
    if not lane_cycles or dev_s <= 0:
        return None
    return 1e6 * dev_s / lane_cycles
