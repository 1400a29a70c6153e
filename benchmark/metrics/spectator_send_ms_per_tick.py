"""Sessions, spectators (sessions/p2p_session.py): ms per host tick in the
host peers' broadcast of confirmed inputs to their spectators, span
session/spectator_send, nested in session/advance (program counter)."""

from benchmark.metrics._span import per, span_sum


def read(run):
    return per(run, span_sum(run, "session/spectator_send"), "host_ticks")
