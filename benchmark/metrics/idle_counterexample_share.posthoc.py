"""Share of the traced window in which the device sat idle while the
host reconstructed an invalid history's counterexample: the program
span ``linear.counterexample`` (its segment rebuild, re-scan dispatches
and readbacks, and host replay).

Each idle instant counts once, under the first program span open at
it (``program_spans.ORDER``), so the five ``idle_*`` shares and the
escalation's sum to ``device_idle_share.posthoc``."""

import program_spans

LAYER = "verdict decode and counterexample"
WRAP = program_spans.WRAP
program_spans.start()


def read(view):
    return program_spans.idle_share(view, "counterexample")
