"""``toot_grid_ms.<job>``: device milliseconds per ``tune`` call in the
programs that build the path tables and count the grid."""
from bench import trace


def read(name, run):
    calls = len(run.window.units)
    if run.trace is None or not calls:
        return None
    t = trace.program_s(run.trace, "toot_grid")
    return None if t is None else 1e3 * t / calls
