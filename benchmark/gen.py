"""The benchmark's one traffic generator.

Copied, so that a later program PR cannot move the yardstick, from
``comdb2_tpu/ops/synth.register_history`` (the simulated atomic CAS
register) and from ``chip_smoke.corrupt`` (the corrupted twin: one ok
read returns a value no op ever wrote; here at a fixed place). It emits plain tuples
``(process, type, f, value)``.
Nothing here imports the program or JAX.

Every size comes from the cell's configuration and traffic files, and
every random choice from ``--seed``: the same seed gives the same
histories, and every seed gives the same sizes.
"""

from __future__ import annotations

import random
from typing import List, Tuple

Op = Tuple[object, str, str, object]

#: a register value no op writes (values are < the config's ``values``)
NEVER_WRITTEN = 1 << 20


def rng_for(seed: int, *stream: int) -> random.Random:
    """A generator of its own for each (seed, stream...) — seeds may be
    larger than 32 bits, and two streams never share draws."""
    return random.Random(":".join(str(int(s)) for s in (seed,) + stream))


def register_history(rng: random.Random, n_procs: int, n_events: int,
                     values: int, p_info: float = 0.0,
                     proc0: int = 0,
                     fs=("read", "write", "cas")) -> List[Op]:
    """A linearizable cas-register history of ``n_events`` events
    (``ops/synth.register_history``): each in-flight op takes effect at
    one random instant between its invoke and its completion."""
    state = None
    # per process: [name, f, value, applied, result]
    procs = [[proc0 + i, None, None, False, None] for i in range(n_procs)]
    next_pid = proc0 + n_procs
    h: List[Op] = []
    while len(h) < n_events:
        pr = rng.choice(procs)
        if pr[1] is None:
            f = pr[1] = rng.choice(fs)
            pr[3] = False
            if f == "read":
                pr[2] = None
            elif f == "write":
                pr[2] = rng.randrange(values)
            else:
                pr[2] = (rng.randrange(values), rng.randrange(values))
            h.append((pr[0], "invoke", f, pr[2]))
        elif not pr[3]:
            pr[3] = True
            f = pr[1]
            if f == "read":
                pr[4] = ("ok", state)
            elif f == "write":
                state = pr[2]
                pr[4] = ("ok", pr[2])
            else:
                expected, new = pr[2]
                if state == expected:
                    state = new
                    pr[4] = ("ok", pr[2])
                else:
                    pr[4] = ("fail", pr[2])
        else:
            if rng.random() < p_info:
                h.append((pr[0], "info", pr[1], pr[2]))
                pr[0] = next_pid
                next_pid += 1
            else:
                typ, v = pr[4]
                h.append((pr[0], typ, pr[1], v if typ == "ok" else pr[2]))
            pr[1] = None
    return h


def corrupt(history: List[Op], at: float) -> Tuple[List[Op], int]:
    """The twin (``chip_smoke.corrupt``, at a fixed place): the first ok
    read at or after share ``at`` of the history now returns a value no
    op ever wrote. ``chip_smoke`` picks the read at random; here the
    place is fixed, so that every seed's twin asks the same
    counterexample work. Returns the twin and the position changed."""
    reads = [i for i, op in enumerate(history)
             if op[1] == "ok" and op[2] == "read"]
    start = int(at * len(history))
    i = next((r for r in reads if r >= start), reads[-1])
    twin = list(history)
    p, _, f, _ = history[i]
    twin[i] = (p, "ok", f, NEVER_WRITTEN)
    return twin, i


def _edn(v) -> str:
    if v is None:
        return "nil"
    if isinstance(v, tuple):
        return "[" + " ".join(_edn(x) for x in v) + "]"
    return str(v)


def to_edn(history: List[Op]) -> str:
    """One EDN op map per line — what ``filetest --service`` submits
    and ``ctest/register.c -j`` emits."""
    return "\n".join(
        f"{{:process {p}, :type :{t}, :f :{f}, :value {_edn(v)}}}"
        for p, t, f, v in history)
