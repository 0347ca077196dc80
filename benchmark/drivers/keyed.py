"""Keyed post-hoc cells: one caller in a closed loop hands whole keyed
histories to ``independent.checker(Linearizable()).check`` and waits
for each key's verdict — the Jepsen register test's
``independent/checker`` over ``checker/linearizable``.

The pool is made in set-up from the seed (``gen_keyed``); entry
``twin_slot`` carries one corrupted key (``twin_key``, at share
``twin_at`` of that key's history), so every pass over the pool asks
one counterexample at a fixed place. Set-up checks the pool
``warmup_passes`` times: the second pass reuses pooled kernel carries
the way the window does. A warm-up that outlasts ``warmup_budget_s``
raises, so a program that can only check these keys on a slow path
ends with an error instead of running on. Neither entry keeps state
between calls: the key split, packing, segments and slot renaming are
redone on every call.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time
from typing import Dict, List, Optional

import gen_keyed
import harness
import reference_keyed


class WarmupBudgetExceeded(BaseException):
    """The warm-up outlasted the traffic file's ``warmup_budget_s``. A
    BaseException, so that no ``except Exception`` on the checker's
    path turns it into an ``unknown`` verdict and runs on."""


class Driver:
    def __init__(self, cell: harness.Cell, seed: int, devices):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.devices = devices
        #: (pool index, answer) of every check the window finished
        self.answers: List[tuple] = []
        self.ops_done = 0
        self.segments = 0
        self.attempted = 0
        self.failed = 0
        self.t_start = self.t_last = self.t_end = 0.0
        self.entry = None

    # -- set-up --------------------------------------------------------

    def generate(self) -> None:
        tr = self.traffic
        self.plain: List[list] = []
        self.twins: Dict[int, int] = {}
        for j in range(int(tr["pool"])):
            twin = None
            if j % int(tr["twin_every"]) == int(tr["twin_slot"]):
                twin = (int(tr["twin_key"]), float(tr["twin_at"]))
            h, changed = gen_keyed.keyed_history(self.seed, j, self.cfg,
                                                 twin)
            if twin is not None:
                self.twins[j] = changed
            self.plain.append(h)
        self.n_ops = [sum(1 for op in h if op[1] == "invoke")
                      for h in self.plain]
        self.n_segs = [sum(1 for op in h if op[1] == "ok")
                       for h in self.plain]
        self.inputs = [self._program_ops(h) for h in self.plain]

    def _program_ops(self, h):
        from comdb2_tpu.ops.kv import tuple_
        from comdb2_tpu.ops.op import Op

        return [Op(p, t, f, tuple_(*v)) for p, t, f, v in h]

    def make_entry(self, control: bool = False):
        """The call the window drives: pool index -> normalized answer
        ``{key: (valid, fail_index)}``. ``control`` puts the reference,
        with the read guarantee dropped, in the program's place."""
        if control:
            return lambda j: reference_keyed.check(self.plain[j],
                                                   weaken="reads")
        from comdb2_tpu.checker import independent
        from comdb2_tpu.checker.checkers import Linearizable
        from comdb2_tpu.models.model import cas_register

        model = cas_register()
        chk = independent.checker(Linearizable())

        def run(j):
            r = chk.check({}, model, self.inputs[j])
            out = {}
            for k, rk in r["results"].items():
                v = rk.get("valid?")
                out[k] = (v, rk.get("op-index", -1) if v is False else -1)
            return out
        return run

    def warm_up(self, control: bool = False) -> None:
        self.entry = self.make_entry(control)
        budget = float(self.traffic["warmup_budget_s"])
        t0 = time.perf_counter()
        with _deadline(budget):
            for _ in range(int(self.traffic["warmup_passes"])):
                for j in range(len(self.inputs)):
                    self.entry(j)
                    spent = time.perf_counter() - t0
                    if spent > budget:
                        raise WarmupBudgetExceeded(
                            f"warm-up took {spent:.1f} s, past its "
                            f"budget of {budget:.0f} s")

    # -- the window ----------------------------------------------------

    def window(self, seconds: float) -> None:
        entry, n = self.entry, len(self.inputs)
        self.t_start = time.perf_counter()
        t_stop = self.t_start + seconds
        j = 0
        while time.perf_counter() < t_stop:
            k = j % n
            self.attempted += 1
            try:
                ans = entry(k)
            except Exception:                    # noqa: BLE001
                ans = None
            t = time.perf_counter()
            self.answers.append((k, ans))
            # only an exact verdict on every key is work done: an
            # unknown (or a missing answer) counts in ``failed`` and
            # never in the rate or the segments checked
            if ans is None or any(v is not True and v is not False
                                  for v, _ in ans.values()):
                self.failed += 1
            else:
                self.segments += self.n_segs[k]
                if t <= t_stop:
                    self.ops_done += self.n_ops[k]
                    self.t_last = t
            j += 1
        self.t_end = time.perf_counter()

    def end_to_end(self) -> Dict[str, Optional[float]]:
        return {"check_ops_per_s": harness.rate(self.ops_done,
                                                self.t_start, self.t_last)}

    def lines(self) -> List[dict]:
        return [{"line": "window", "histories": self.attempted,
                 "keys_per_history": int(self.cfg["keys"]),
                 "ops_completed": self.ops_done,
                 "t_last_completion_s": self.t_last - self.t_start,
                 "window_end_s": self.t_end - self.t_start,
                 "twin_changed_at": self.twins}]

    def release(self) -> None:
        self.inputs = None
        self.entry = None

    # -- the reference -------------------------------------------------

    def references(self, indices) -> Dict[int, dict]:
        return {j: reference_keyed.check(self.plain[j])
                for j in sorted(set(indices))}


class _deadline:
    """Raise :class:`WarmupBudgetExceeded` in the main thread once
    ``budget`` seconds have passed (SIGALRM, which a Python-level wait
    notices), and end the process 60 s later if a call into native
    code still has not returned."""

    GRACE_S = 60.0

    def __init__(self, budget: float):
        self.budget = budget
        self._timer = None
        self._old = None

    def _alarm(self, *_):
        raise WarmupBudgetExceeded(
            f"warm-up still running after its budget of "
            f"{self.budget:.0f} s")

    def _kill(self):
        print(f"keyed driver: warm-up stuck {self.GRACE_S:.0f} s past "
              f"its budget of {self.budget:.0f} s; exiting",
              file=sys.stderr, flush=True)
        os._exit(3)

    def __enter__(self):
        if threading.current_thread() is threading.main_thread():
            self._old = signal.signal(signal.SIGALRM, self._alarm)
            signal.setitimer(signal.ITIMER_REAL, self.budget)
        self._timer = threading.Timer(self.budget + self.GRACE_S,
                                      self._kill)
        self._timer.daemon = True
        self._timer.start()
        return self

    def __exit__(self, *exc):
        self._timer.cancel()
        if self._old is not None:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, self._old)
        return False
