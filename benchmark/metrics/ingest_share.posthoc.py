"""Share of the window the host spends in the checker entry's ingest:
packing and the memoized model.

Read from benchmark-side wrappers around these functions, installed
only in the traced run. Where one of them no longer exists the metric
reads nothing."""

LAYER = "checker entry and host ingest"
WRAP = {"ingest": [
    "comdb2_tpu.ops.packed.pack_history",
    "comdb2_tpu.checker.linear.pack_history",
    "comdb2_tpu.checker.linear.make_memo",
]}


def read(view):
    return view.spans.share("ingest", view.t0, view.t1)
