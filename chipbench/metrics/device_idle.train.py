"""Share of the window in which no operation ran on the device, from the
profiler trace (``chipbench.trace``)."""

from chipbench.trace import idle_share


def read(rec):
    return idle_share(rec.trace)
