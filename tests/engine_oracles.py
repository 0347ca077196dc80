"""Direct engine runs the batch tests compare ``check_batch`` against.

``check_batch`` picks its engine from the shapes alone, so a test that
needs one particular engine's verdicts calls that engine here, on the
same inputs the batch path builds: the key-packed engine on
``segment_batch`` output, and the event-level vmap oracle on dense
step streams. :func:`last_resort_nt` gives the declared table width at
which ``check_batch`` itself takes its last resort.
"""

import numpy as np

from comdb2_tpu.checker import linear_jax as LJ
from comdb2_tpu.checker.batch import segment_batch
from comdb2_tpu.utils import next_pow2


def _table(batch):
    ns, nt = batch.memo.n_states, batch.memo.n_transitions
    succ = LJ.pad_succ(batch.memo.succ, next_pow2(ns), next_pow2(nt))
    return succ, {"n_states": ns, "n_transitions": nt}


def keys_verdicts(batch, F, mesh=None):
    """``(status, fail_at, n_final)`` of the key-packed engine on the
    batch's segment tensors — sharded over ``mesh`` (whose size must
    divide ``len(batch)``) when one is given. fail_at in history-index
    terms, like ``check_batch``'s."""
    sb = segment_batch(batch)
    succ, sizes = _table(batch)
    segs = (sb.inv_proc, sb.inv_tr, sb.ok_proc, sb.depth)
    P = next_pow2(batch.P, 2)
    if mesh is None:
        out = LJ.check_device_keys(succ, *segs, B=len(batch), F=F, P=P,
                                   **sizes)
    else:
        out = LJ.check_device_keys_sharded(mesh, succ, *segs,
                                           B=len(batch), F=F, P=P,
                                           **sizes)
    status, fail_seg, n_final = (np.asarray(x) for x in out)
    fail_at = np.array([sb.seg_index[b, s] if s >= 0 else -1
                        for b, s in enumerate(fail_seg)], np.int64)
    return status, fail_at, n_final


def dense_streams(batch):
    """``(kind, proc, tr)`` int32[B, n_pad]: each history's per-event
    step stream (``make_stream``), transition ids remapped into the
    batch's union table, padded to one pow2 length — the input of the
    event-level oracles ``check_device_batch`` / ``check_sharded``."""
    n_pad = next_pow2(max(len(p) for p in batch.packeds))
    kinds, procs, trs = [], [], []
    for p, remap in zip(batch.packeds, batch.remaps):
        s = LJ.make_stream(p, n_pad=n_pad)
        kind = np.asarray(s.kind)
        tr = np.asarray(s.tr).copy()
        inv = kind == LJ.K_INVOKE
        if remap.size:
            tr[inv] = remap[tr[inv]]
        kinds.append(kind)
        procs.append(np.asarray(s.proc))
        trs.append(tr)
    return np.stack(kinds), np.stack(procs), np.stack(trs)


def last_resort_nt(batch):
    """The smallest pow2 ``n_transitions_pad`` at which the key-packed
    engine's 62-bit key cannot hold this batch's configs (at any batch
    width), so ``check_batch`` runs the vmapped segmented search. The
    batch's slot width must stay below the MXU engine's crossover."""
    P = next_pow2(batch.P, 2)
    nt = next_pow2(batch.memo.n_transitions)
    while LJ.KeyLayout(1, batch.memo.n_states, nt, P).fits:
        nt *= 2
    return nt
