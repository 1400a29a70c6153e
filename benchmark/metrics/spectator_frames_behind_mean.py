"""Sessions, spectators (sessions/spectator_session.py): the mean of
ggrs_spectator_frames_behind, the frames a spectator still trails the host
peer's inputs it has received after each advance: the staleness a viewer
sees (program counter). None where the program keeps no such histogram or
it saw no advance."""


def read(run):
    hist = run.counters.get("ggrs_spectator_frames_behind")
    if not hist:
        return None
    count = sum(v["count"] for v in hist["values"].values())
    if not count:
        return None
    return sum(v["sum"] for v in hist["values"].values()) / count
