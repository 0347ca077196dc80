"""Share of the traced window in which the device sat idle while a key
was checked again on its own (an invalid key's counterexample, or a
key past the kernel's top rung): the program span
``independent.recheck``.

Each idle instant counts once, under the first group of program spans
open at it (``keyed_spans.ORDER``: recheck, split, stream build,
dispatch), or as unattributed: with the dispatch share that the
``keyed_idle`` line prints, these sum to
``device_idle_share.keyed``."""

import keyed_spans

LAYER = "per-key re-check"
WRAP = keyed_spans.WRAP
keyed_spans.start()


def read(view):
    return keyed_spans.idle_share(view, "recheck")
