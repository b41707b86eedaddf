"""``compiles.<job>``: executables compiled, or loaded from the
persistent cache, inside the window (the program's zero-length
``repro.compile`` spans).  A program without ``repro.core.obs`` emits no
such span, and reads nothing rather than 0."""
import importlib.util

from bench import xplane


def read(name, run):
    tr = xplane.for_run(run)
    if tr is None or importlib.util.find_spec("repro.core.obs") is None:
        return None
    return len(xplane.window_spans(tr, run.trace, "repro.compile"))
