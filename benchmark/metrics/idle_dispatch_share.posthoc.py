"""Share of the traced window in which the device sat idle while the
host prepared, dispatched or read back the fused kernel for a verdict:
the program spans ``pallas.prepare`` and ``pallas.scan``, outside a
counterexample.

Each idle instant counts once, under the first program span open at
it (``program_spans.ORDER``), so the five ``idle_*`` shares and the
escalation's sum to ``device_idle_share.posthoc``."""

import program_spans

LAYER = "kernel dispatch and readback"
WRAP = program_spans.WRAP
program_spans.start()


def read(view):
    return program_spans.idle_share(view, "dispatch")
