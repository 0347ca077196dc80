"""Share of the traced window in which the device sat idle while the
host was in the checker's ingest: the program span ``linear.pack``
(packing and the memoized model).

Each idle instant counts once, under the first program span open at
it (``program_spans.ORDER``), so the five ``idle_*`` shares and the
escalation's sum to ``device_idle_share.posthoc``."""

import program_spans

LAYER = "checker entry and host ingest"
WRAP = program_spans.WRAP
program_spans.start()


def read(view):
    return program_spans.idle_share(view, "ingest")
