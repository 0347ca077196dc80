"""Device milliseconds of the fused and stream Pallas kernels per
1,000 segments checked in the traced window.

Kernel time is the union of the device intervals of the Pallas
kernels' ops (Mosaic ``tpu_custom_call``s; the kernels carry no
``name=`` yet) in the profiler trace. A segment is one ok completion of a history
checked in the window (the segment stream's length), counted from the
inputs. No kernel event, no reading."""

import trace_reduce

LAYER = "kernel"


def read(view):
    tr = view.trace
    if tr is None or not view.driver.segments:
        return None
    s = tr.op_seconds(trace_reduce.is_kernel)
    if s <= 0.0:
        return None
    return 1e3 * s / (view.driver.segments / 1000.0)
