"""The plain reference: a dict from key to value, independent of the store.

It starts as the loaded records (made from the seed, never read back from
the store) and applies every write in the order the closed loop submitted
it.  ``KvBatchServer`` keeps per-key program order (a read never passes an
earlier write to the same key, and never sees a later one), and the
configurations state that an acknowledged write is visible to every later
read at once, so each answer has exactly one right value: the reference's
value for that key at the moment the request was submitted.

Where the traffic tags its writes with epochs and the configuration
retains the newest ``retain_epochs`` of them, the store retires the rest
(``PruneController.step``: a WAL segment drops once its whole epoch range
lies below the floor, newest epoch written - retain_epochs + 1, at a slice
that starts with no relocation pass in flight; a relocation pass retires
the expired records it scans).  A put of epoch e at or above the final
floor is retained and read back exactly; one further below may be gone.
Untagged records (epoch 0, the loaded ones) are never retired.  A put is
certainly gone once its segment's newest epoch is below the floor.  A
segment holds at most ``segment_records`` records, so one written in place
sits in a segment whose newest epoch is at most e + ceil(segment_records /
puts per epoch); relocation copies a record to the tail only while it is
retained, at most retain_epochs - 1 epochs after e.  So every put of epoch
e <= floor - retain_epochs - ceil(segment_records / puts per epoch) is gone
once the server has run a reclamation slice with no pass in flight.

Every compared number is a count whose limit is 0: the comparison is exact.
"""
from __future__ import annotations

import math

from .traffic import OPS

GET, EXISTS, PUT = (OPS.index(k) for k in ("get", "exists", "put"))


class DictOracle:
    def __init__(self, keys, values):
        self.state = dict(zip(keys, values))
        self.epoch: dict = {}        # key -> epoch of its last tagged put

    def replay(self, seq, reqs, epoch_requests=None) -> dict:
        """Replays the requests ``reqs`` (submission order, the i-th being
        sequence entry i mod len(seq)) and counts how the served
        answers depart from the reference.  With ``epoch_requests``, put i
        carries epoch 1 + i // epoch_requests.

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
                if epoch_requests:
                    self.epoch[r.key] = 1 + i // epoch_requests
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

    def retention(self, retain: int, segment_records: int,
                  puts_per_epoch: int) -> tuple:
        """(floor, retained, gone): the final floor, the tagged keys at or
        above it, and the tagged keys that must be gone (module doc)."""
        if not self.epoch:
            return None, [], []
        floor = max(self.epoch.values()) - retain + 1
        last = floor - retain - math.ceil(segment_records / puts_per_epoch)
        retained = [k for k, e in self.epoch.items() if e >= floor]
        gone = [k for k, e in self.epoch.items() if e <= last]
        return floor, retained, gone

    @staticmethod
    def count_present(engine, keys, batch: int = 4096) -> int:
        """How many of ``keys`` ``engine.multi_get`` still finds."""
        keys = list(keys)
        return sum(v is not None for i in range(0, len(keys), batch)
                   for v in engine.multi_get(keys[i:i + batch]))
