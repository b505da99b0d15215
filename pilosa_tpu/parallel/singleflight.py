"""Request collapsing for identical concurrent read queries.

The reference serves concurrent identical queries from goroutines over
one shared mmap — duplicated work costs only CPU.  On an accelerator
every duplicate is a full dispatch + readback, and readbacks
SERIALIZE, so N clients asking the same TopN/Sum simultaneously would
burn N serialized readback slots for one answer.  This is the groupcache-style
singleflight: the first caller computes; concurrent callers with the
same key wait and share the result (errors propagate to every waiter;
results are NOT cached — the moment the flight lands, the next caller
recomputes against fresh data, so writes are never masked)."""

from __future__ import annotations

import threading
from typing import Callable, Dict


class _Flight:
    __slots__ = ("event", "result", "error")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.error = None


class SingleFlight:
    # A flight that outlives this is wedged (stuck collective): fail the
    # waiters rather than hanging HTTP threads forever.
    WAIT_TIMEOUT = 300.0

    def __init__(self):
        self._lock = threading.Lock()
        self._flights: Dict[tuple, _Flight] = {}
        # Telemetry (tests assert on shared counts).
        self.flights = 0
        self.shared = 0

    def do(self, key: tuple, fn: Callable):
        """Run ``fn()`` once per concurrent burst of callers with the
        same ``key``; every caller gets its result (or its exception)."""
        with self._lock:
            f = self._flights.get(key)
            if f is not None:
                self.shared += 1
                leader = False
            else:
                f = _Flight()
                self._flights[key] = f
                self.flights += 1
                leader = True
        if not leader:
            if not f.event.wait(self.WAIT_TIMEOUT):
                raise RuntimeError("singleflight wait timed out")
            if f.error is not None:
                raise f.error
            return f.result
        return self._fly({key: f}, [key], lambda: [fn()])[0]

    def do_all(self, keys, fn: Callable):
        """``do`` for a run of keys that ONE ``fn()`` answers together
        (a list of results, in key order); concurrent callers of ``do``
        with one of the keys share that key's result.  All or nothing:
        when any key already has a flight up, nothing is led, ``fn`` is
        not run and None is returned — the caller takes its one-key-a-
        call path and joins the twin there (waiting for it here and
        flying the rest afterwards would be two readbacks, not one)."""
        with self._lock:
            if any(k in self._flights for k in keys):
                return None
            flights = {k: _Flight() for k in keys}
            self._flights.update(flights)
            self.flights += len(flights)
        return self._fly(flights, keys, fn)

    def _fly(self, flights: Dict[tuple, _Flight], keys, fn: Callable) -> list:
        """The leader's half: ``fn()`` answers ``keys`` (a list, in
        their order; a key may repeat); result or exception reaches
        each flight's waiters."""
        try:
            results = fn()
            for k, r in zip(keys, results):
                flights[k].result = r
            return results
        except BaseException as e:
            for f in flights.values():
                f.error = e
            raise
        finally:
            with self._lock:
                for k in flights:
                    self._flights.pop(k, None)
            for f in flights.values():
                f.event.set()
