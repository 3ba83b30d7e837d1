"""Milliseconds per step in which a device runs a collective (the FSDP
all-gathers and reduce-scatters) with no other operation running on it,
averaged over the devices, from the trace."""


def read(run):
    s = run.trace.collective_exposed_s()
    return None if s is None else 1e3 * s / len(run.trace.steps)
