"""Share of the traced window in which the device sat idle while none
of the keyed path's program spans was open: the caller's loop, the
split's result, the verdict's decode.

Each idle instant counts once, under the first group of program spans
open at it (``keyed_spans.ORDER``: recheck, split, stream build,
dispatch), or as unattributed: with the dispatch share that the
``keyed_idle`` line prints, these sum to
``device_idle_share.keyed``."""

import keyed_spans

LAYER = "device"
WRAP = keyed_spans.WRAP
keyed_spans.start()


def read(view):
    return keyed_spans.idle_share(view, "unattributed")
