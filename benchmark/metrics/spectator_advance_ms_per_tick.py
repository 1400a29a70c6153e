"""Sessions, spectators (sessions/spectator_session.py): ms per host tick
inside SpectatorSession.advance_frame, span spectator/advance summed over
every hosted spectator (program counter)."""

from benchmark.metrics._span import per, span_sum


def read(run):
    return per(run, span_sum(run, "spectator/advance"), "host_ticks")
