"""Seconds of the window spent building executables: compiles of
shapes the data brings up for the first time, and loads of executables
from the persistent compilation cache (JAX's monitoring events)."""


def read(r):
    comp = r.run.compiles
    return sum(s for s, _ in comp.between(r.run.t0, r.run.t1)) + sum(
        s for s, _ in comp.between(r.run.t0, r.run.t1, loads=True))
