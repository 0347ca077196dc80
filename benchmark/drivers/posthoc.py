"""Post-hoc cells: one caller in a closed loop hands whole histories to
the checker entry and waits for each verdict: ``Linearizable().check``
on a single-register history (which calls ``linear.analysis``).

The pool of histories is made in set-up from the seed and checked once
there (warm-up: every shape the window uses, the twin's counterexample
path included); the window then cycles through it. Neither entry keeps
state between calls: packing, segments and the slot renaming are
rebuilt from the Op list on every call (their caches hang off per-call
objects), so a second check of a pool entry does the same work as the
first.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import gen
import harness
import reference


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
        cfg, tr = self.cfg, self.traffic
        events = 2 * int(cfg["ops_per_key"])
        mix = tuple(cfg["op_mix"])
        self.plain: List[list] = []
        self.twins: List[int] = []
        for j in range(int(tr["pool"])):
            rng = gen.rng_for(self.seed, j)
            h = gen.register_history(rng, int(cfg["procs_per_key"]),
                                     events, int(cfg["values"]),
                                     float(cfg["p_info"]), fs=mix)
            if j % int(tr["twin_every"]) == int(tr["twin_slot"]):
                h, _ = gen.corrupt(h, float(tr["twin_at"]))
                self.twins.append(j)
            self.plain.append(h)
        self.n_ops = [sum(1 for op in h if op[1] == "invoke")
                      for h in self.plain]
        self.n_segs = [sum(1 for op in h if op[1] == "ok")
                       for h in self.plain]
        self.inputs = [self._program_ops(h) for h in self.plain]

    def _program_ops(self, h):
        from comdb2_tpu.ops.op import Op

        return [Op(p, t, f, v) for p, t, f, v in h]

    def make_entry(self, control: bool = False):
        """The call the window drives: pool index -> normalized answer
        ``{key: (valid, fail_index)}``. ``control`` puts the reference,
        with the read guarantee dropped, in the program's place."""
        if control:
            return lambda j: {0: reference.check(self.plain[j],
                                                 weaken="reads")}
        from comdb2_tpu.checker.checkers import Linearizable
        from comdb2_tpu.models.model import cas_register

        model = cas_register()
        chk = Linearizable()

        def run(j):
            r = chk.check({}, model, self.inputs[j])
            v = r.get("valid?")
            return {0: (v, r.get("op-index", -1) if v is False else -1)}
        return run

    def warm_up(self, control: bool = False) -> None:
        self.entry = self.make_entry(control)
        for j in range(len(self.inputs)):
            self.entry(j)

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
                 "ops_completed": self.ops_done,
                 "t_last_completion_s": self.t_last - self.t_start,
                 "window_end_s": self.t_end - self.t_start}]

    def release(self) -> None:
        self.inputs = None
        self.entry = None

    # -- the reference -------------------------------------------------

    def references(self, indices) -> Dict[int, dict]:
        out = {}
        for j in sorted(set(indices)):
            out[j] = {0: reference.check(self.plain[j])}
        return out
