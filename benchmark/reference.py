"""The plain reference: linearizability of a CAS register.

A straightforward just-in-time linearization search (the knossos
``linear`` algorithm, Lowe's JIT linearizability): a configuration is
the register's value and the set of pending calls already linearized.
At each ok completion every configuration is closed under linearizing
pending calls, those that have not linearized the completing call are
dropped, and the call retires. The history is invalid at the first ok
completion that leaves no configuration.

The model is knossos's ``cas-register`` (``knossos/model.clj:95-116``):
``write v`` sets the value, ``cas [e n]`` sets ``n`` where the value
is ``e`` and is illegal elsewhere, ``read v`` is legal where ``v`` is
nil or the value. Failed calls never happened; ``info`` calls stay
pending for ever. The initial value is nil.

It imports nothing of the program and reads only the generator's
plain tuples ``(process, type, f, value)``.

``weaken="reads"`` is the control: it drops the guarantee that a read
returns the register's value (every read is legal), the one guarantee
the benchmark's twins break.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

Op = Tuple[object, str, str, object]


def _step(state, f, value, weaken: Optional[str]):
    """(legal, new state) of one call."""
    if f == "write":
        return True, value
    if f == "cas":
        if value is None:
            return False, state
        e, n = value
        return (state == e), n
    if f == "read":
        if weaken == "reads":
            return True, state
        return (value is None or value == state), state
    return False, state


def check(history: List[Op], weaken: Optional[str] = None
          ) -> Tuple[bool, int]:
    """``(valid, fail_index)``: ``fail_index`` is the position of the ok
    completion at which no configuration survives, or -1 when valid."""
    n = len(history)
    # pair each invoke with its completion: a failed call is skipped,
    # an ok call takes the completion's value (knossos history/complete)
    value_of: Dict[int, object] = {}
    skip = set()
    open_at: Dict[object, int] = {}
    for i, (p, t, f, v) in enumerate(history):
        if t == "invoke":
            open_at[p] = i
            value_of[i] = v
        elif t in ("ok", "fail"):
            j = open_at.pop(p)
            if t == "fail":
                skip.add(j)
                skip.add(i)
            else:
                value_of[j] = v
        # info: the call stays pending for ever

    # configuration: (value, frozenset of linearized pending call ids)
    configs = {(None, frozenset())}
    calls: Dict[int, Tuple[str, object]] = {}   # open call id -> (f, v)
    call_of: Dict[object, int] = {}              # process -> call id
    for i in range(n):
        if i in skip:
            continue
        p, t, f, _ = history[i]
        if t == "invoke":
            calls[i] = (f, value_of[i])
            call_of[p] = i
            continue
        if t != "ok":
            call_of.pop(p, None)                 # info: stays in calls
            continue
        c = call_of.pop(p)
        survivors = set()
        seen = set(configs)
        todo = list(configs)
        while todo:
            state, done = todo.pop()
            if c in done:
                survivors.add((state, done - {c}))
                continue
            for k, (kf, kv) in calls.items():
                if k in done:
                    continue
                legal, nxt = _step(state, kf, kv, weaken)
                if not legal:
                    continue
                cfg = (nxt, done | {k})
                if cfg not in seen:
                    seen.add(cfg)
                    todo.append(cfg)
        del calls[c]
        if not survivors:
            return False, i
        configs = survivors
    return True, -1
