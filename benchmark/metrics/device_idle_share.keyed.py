"""Device idle share of the traced window in keyed cells:
1 - (union of device op intervals / window), mean over the cell's
devices, from the profiler trace."""

LAYER = "device"


def read(view):
    return None if view.trace is None else view.trace.idle_share()
