"""Device milliseconds of the fused kernel per 1,000 segments with an
exact verdict in the traced window of a keyed cell.

Kernel time is the union of the device intervals of every
``seg_frontier`` call (the stream that decides every key, and the
checks again of invalid keys with their counterexamples), from the
profiler trace. A segment is one ok completion of a history whose
every key got an exact verdict in the window, counted from the
inputs. No kernel event, no reading."""

import keyed_spans

LAYER = "kernel"


def read(view):
    s = keyed_spans.kernel_s(view.trace)
    if s is None or not view.driver.segments:
        return None
    return 1e3 * s / (view.driver.segments / 1000.0)
