"""Share of the traced window in which the device sat idle while none
of the program's layer spans was open: the caller's loop, the verdict's
decode, the successor table's transfer.

Each idle instant counts once, under the first program span open at
it (``program_spans.ORDER``), so the five ``idle_*`` shares and the
escalation's sum to ``device_idle_share.posthoc``."""

import program_spans

LAYER = "device"
WRAP = program_spans.WRAP
program_spans.start()


def read(view):
    return program_spans.idle_share(view, "unattributed")
