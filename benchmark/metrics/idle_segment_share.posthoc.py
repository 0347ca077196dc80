"""Share of the traced window in which the device sat idle while the
host built segments and renamed slots for a verdict: the program span
``linear.segments``, outside a counterexample.

Each idle instant counts once, under the first program span open at
it (``program_spans.ORDER``), so the five ``idle_*`` shares and the
escalation's sum to ``device_idle_share.posthoc``."""

import program_spans

LAYER = "segment and slot renaming"
WRAP = program_spans.WRAP
program_spans.start()


def read(view):
    return program_spans.idle_share(view, "segment")
