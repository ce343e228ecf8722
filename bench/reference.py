"""The plain reference: a dict from key to value, independent of the store.

It starts as the loaded records (made from the seed, never read back from
the store) and applies every write in the order the closed loop submitted
it.  ``KvBatchServer`` keeps per-key program order (a read never passes an
earlier write to the same key, and never sees a later one), and the
configurations state that an acknowledged write is visible to every later
read at once, so each answer has exactly one right value: the reference's
value for that key at the moment the request was submitted.

Every compared number is a count whose limit is 0: the comparison is exact.
"""
from __future__ import annotations

from .traffic import OPS

GET, EXISTS, PUT = (OPS.index(k) for k in ("get", "exists", "put"))


class DictOracle:
    def __init__(self, keys, values):
        self.state = dict(zip(keys, values))

    def replay(self, seq, reqs) -> dict:
        """Replays the requests ``reqs`` (submission order, the i-th being
        sequence entry i mod len(seq)) and counts how the served
        answers depart from the reference.

        Returns ``wrong_answers`` (a get's value or an exists' flag that
        differs) and ``unanswered`` (never marked done).  A request that
        carried an error is counted by the caller; a write with an error
        was not acknowledged, so the reference leaves its key as it was."""
        wrong = unanswered = 0
        n = len(seq)
        state = self.state
        for i, r in enumerate(reqs):
            j = i % n
            op = seq.op[j]
            if not r.done:
                unanswered += 1
                continue
            if r.error is not None:
                continue
            if op == PUT:
                state[r.key] = seq.value[j]
                continue
            want = state.get(r.key)
            if op == GET:
                wrong += r.value != want
            else:
                wrong += r.found != (want is not None)
        return {"wrong_answers": wrong, "unanswered": unanswered}

    def read_back(self, engine, keys, batch: int = 4096) -> int:
        """Reads ``keys`` back through ``engine.multi_get`` and counts the
        values that differ from the reference's."""
        keys = list(keys)
        wrong = 0
        for i in range(0, len(keys), batch):
            part = keys[i:i + batch]
            got = engine.multi_get(part)
            wrong += sum(g != self.state.get(k) for k, g in zip(part, got))
        return wrong
