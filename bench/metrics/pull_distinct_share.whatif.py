"""Share of the ring slots the what-if events pull that are distinct
rows, in percent: the program's counter ``replay_ring.pull_rows`` (the
distinct ``ts % K`` among each event's pulled indices) over
``replay_ring.pull_slots`` (its c pulled indices).  The counters are the
program's totals in this process, so the share is over every event the
run replayed: the warm-up's segments and the window call's, up to the
window's end.  Absent where the program keeps no such counters."""

SLOTS = "replay_ring.pull_slots"
ROWS = "replay_ring.pull_rows"


def read(ctx):
    try:
        from repro import telemetry
    except ImportError:
        return None
    counts = telemetry.counters()
    if not counts.get(SLOTS) or ROWS not in counts:
        return None
    return 100.0 * counts[ROWS] / counts[SLOTS]
