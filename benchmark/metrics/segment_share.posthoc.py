"""Share of the window the host spends building segments and renaming
slots (``make_segments``, ``remap_slots``).

Read from benchmark-side wrappers installed only in the traced run;
where one of the functions no longer exists the metric reads nothing."""

LAYER = "segment and slot renaming"
WRAP = {"segment": [
    "comdb2_tpu.checker.linear_jax.make_segments",
    "comdb2_tpu.checker.linear_jax.remap_slots",
]}


def read(view):
    return view.spans.share("segment", view.t0, view.t1)
