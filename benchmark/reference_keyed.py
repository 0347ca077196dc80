"""The plain reference for keyed histories: ``independent/checker``
over linearizability of a CAS register (``independent.clj:252-300``).

It splits the history by key in one pass (an op whose value is a
``(key, value)`` tuple goes to that key, unwrapped; an op with any
other value goes to every key, in order, ``independent.clj:240-250``
— the keyed generator wraps every value, so none does) and runs the
single-register reference (``reference.check``) on each key's
subhistory. It imports nothing of the program.

The answer is ``{key: (valid, fail_index)}``. ``fail_index`` is the
position, inside the key's own subhistory, of the ok completion at
which no configuration survives, or -1 when the key is valid — the
convention of the program's per-key ``op-index``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import reference

Op = reference.Op


def split(history: List[Op]) -> Dict[object, List[Op]]:
    """Each key's subhistory, keys in first-appearance order."""
    keys: Dict[object, None] = {}
    for _, _, _, v in history:
        if isinstance(v, tuple):
            keys.setdefault(v[0], None)
    subs: Dict[object, List[Op]] = {k: [] for k in keys}
    for p, t, f, v in history:
        if isinstance(v, tuple):
            subs[v[0]].append((p, t, f, v[1]))
        else:
            for sub in subs.values():
                sub.append((p, t, f, v))
    return subs


def check(history: List[Op], weaken: Optional[str] = None
          ) -> Dict[object, Tuple[bool, int]]:
    return {k: reference.check(sub, weaken=weaken)
            for k, sub in split(history).items()}
