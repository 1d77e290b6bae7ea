"""One door for work that must leave one at a time and may be shared: a
group commit. The volume server's heartbeats go through one (composed and
sent in order, callers that wait together share the next), and so do the
marks of `ec.encode`'s checkpoint (one write, one fsync, one rename for all
the volumes that finished while the write before was running)."""

from __future__ import annotations

import threading
from typing import Callable, Optional


class _Round:
    """One run through the door: what its callers handed in, whether it has
    ended, and what it raised."""

    __slots__ = ("items", "done", "error")

    def __init__(self):
        self.items: list = []
        self.done = False
        self.error: Optional[BaseException] = None


class Door:
    """`through(item)` returns after a run of `run(items)` that BEGAN after
    the call did and that held `item`; what the run raised, every caller of
    it raises. Runs never overlap and leave in the order they began. A caller
    that arrives while a run is on its way joins the NEXT one, and callers
    that wait together share it; a lone caller runs at once, on its own
    thread. Nothing is deferred: a run starts as soon as the one before it
    has ended, on the thread of one of its callers."""

    def __init__(self, run: Callable[[list], None]):
        self._run = run
        self._cond = threading.Condition()
        self._flying: Optional[_Round] = None
        self._next = _Round()  # not begun: callers join it until one of them runs it
        self._closed = False

    def through(self, item=None) -> int:
        """-> how many callers the run served (0: the door was closed first)."""
        with self._cond:
            rnd = self._next
            rnd.items.append(item)
            while self._flying is not None and not rnd.done and not self._closed:
                self._cond.wait()
            if self._closed and not rnd.done:
                return 0
            lead = not rnd.done  # nothing on its way: this caller runs the round it joined
            if lead:
                self._flying, self._next = rnd, _Round()
        if lead:
            try:
                self._run(rnd.items)
            except BaseException as e:  # noqa: BLE001 — every caller's of this round, raised below
                rnd.error = e
            finally:
                with self._cond:
                    rnd.done = True
                    self._flying = None
                    self._cond.notify_all()
        if rnd.error is not None:
            raise rnd.error
        return len(rnd.items)

    def close(self) -> None:
        """No further run begins; whoever waits for one returns 0 now."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
