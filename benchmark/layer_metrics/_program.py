"""What the readers take from inside the program. Not a metric: no
``.json`` beside it. The import below is the only one the readers make of
the program; a program that keeps no dispatch log (a commit from before it
had one) gives no records, and each reader then returns nothing."""

from __future__ import annotations


def window_dispatches(obs: dict) -> list:
    """The program's committed dispatch records whose pass began inside
    the window. A record's ``t0`` and the window's are both
    ``time.perf_counter()`` of this process; the log outlives the app."""
    try:
        from gofr_tpu.flight_recorder import dispatch_log
    except ImportError:
        return []
    t0, t1 = obs["t0"], obs["t0"] + obs["seconds"]
    return [r for r in dispatch_log().records() if t0 <= r["t0"] < t1]
