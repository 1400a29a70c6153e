"""Sessions (sessions/p2p_session.py): ms per host tick inside
P2PSession.advance_frame, span session/advance summed over every hosted
session, the sessions' own GGRS logic within host/advance (program
counter)."""

from benchmark.metrics._span import per, span_sum


def read(run):
    return per(run, span_sum(run, "session/advance"), "host_ticks")
