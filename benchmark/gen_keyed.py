"""Keyed traffic: the Jepsen comdb2 register test's independent keys
(``core.clj:567-613``, ``register-tester``).

Each key is one run of the benchmark's register generator
(``gen.register_history``) with its own stream of draws: 10 clients,
processes ``10k .. 10k+9`` for key ``k``, the op mix and sizes of the
configuration. Values are wrapped as ``(k, v)`` tuples, as
``jepsen.independent`` wraps them, and the keys follow one another in
the history (one group of clients per key). Nothing here imports the
program or JAX.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import gen

Op = gen.Op


def key_history(seed: int, j: int, k: int, cfg: dict) -> List[Op]:
    """Key ``k``'s plain history in pool entry ``j`` (values unwrapped)."""
    n = int(cfg["procs_per_key"])
    return gen.register_history(
        gen.rng_for(seed, j, k), n, 2 * int(cfg["ops_per_key"]),
        int(cfg["values"]), float(cfg["p_info"]), proc0=n * k,
        fs=tuple(cfg["op_mix"]))


def wrap(k, history: List[Op]) -> List[Op]:
    return [(p, t, f, (k, v)) for p, t, f, v in history]


def keyed_history(seed: int, j: int, cfg: dict,
                  twin: Optional[Tuple[int, float]] = None
                  ) -> Tuple[List[Op], int]:
    """Pool entry ``j``: every key's history, wrapped and concatenated
    in key order. ``twin = (key, at)`` corrupts that key's own history
    with ``gen.corrupt(..., at)``. Returns the history and the changed
    position inside the twin key's history (-1 without a twin)."""
    out: List[Op] = []
    changed = -1
    for k in range(int(cfg["keys"])):
        h = key_history(seed, j, k, cfg)
        if twin is not None and k == twin[0]:
            h, changed = gen.corrupt(h, twin[1])
        out.extend(wrap(k, h))
    return out, changed
