"""Device milliseconds of the fused kernel on the verdict path per
1,000 segments checked in the traced window.

Kernel time is the union of the device intervals of the Pallas
kernels' ops (Mosaic ``tpu_custom_call``s) that ran while the program
span ``pallas.scan`` was open and ``linear.counterexample`` was not:
the scan that decides the verdict, without the counterexample's
re-scan, which ``kernel_ms_per_kseg.posthoc`` also counts. Segments are
counted as that metric counts them. No program span or kernel event,
no reading."""

import program_spans

LAYER = "kernel"
WRAP = program_spans.WRAP
program_spans.start()


def read(view):
    r = program_spans.report(view)
    if r is None or not r["fused_kernel_s"] or not view.driver.segments:
        return None
    return 1e3 * r["fused_kernel_s"] / (view.driver.segments / 1000.0)
