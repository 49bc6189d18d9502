"""The engine's queue of admitted-but-unseated requests, indexed
(ISSUE 46).

No reference counterpart. The queue reads as ONE line in arrival order
(a requeued request, `appendleft`, goes to its front), and that order
is what every answer below is defined on; but no answer walks it:

* **the next request** (`pop_next`: highest priority first, FIFO
  within a priority, a requeued request ahead of its priority's line)
  is the left end of the highest priority's own deque — a queue of one
  priority pops from the left;
* **the expired ones** (`pop_expired`) come off a heap of expiry
  times that holds only the requests that carry one: a round with
  nothing due costs one comparison, whether or not requests carry
  deadlines;
* **the ids in flight** (`holds`) are the keys of a dict kept beside
  the lines.

Removal from the middle of a line (an expiry, `remove`) leaves the
entry in place with its request taken out; such an entry is dropped
when it reaches an end of its line, so both ends of every line are
live. `examined` counts the entries (of the lines and of the heap)
that any answer looked at: the `admit` span's `scanned` is its delta.

Pure host bookkeeping: no clock (the caller hands `now` and every
expiry time in), no RNG, no device.
"""

from __future__ import annotations

import heapq
import math
from collections import deque
from typing import Dict, Iterator, List, Tuple


class RequestQueue:
    """Requests waiting for a slot. `len`, truthiness and in-order
    iteration read it as the one line it stands for."""

    def __init__(self):
        # priority -> its line, a deque of entries [place, request,
        # expiry time] by ascending place; a removed entry's request is
        # None. No line is empty and both ends of each are live
        self._lines: Dict[int, deque] = {}
        self._live: Dict[int, list] = {}          # request id -> entry
        # (expiry time, place, entry) of the entries that carry one;
        # an entry whose request left the queue dies here lazily
        self._expiries: List[Tuple[float, int, list]] = []
        self._front, self._back = -1, 0           # the next places
        self.examined = 0

    # ------------------------------------------------------------ views
    def __len__(self) -> int:
        return len(self._live)

    def holds(self, request_id) -> bool:
        return request_id in self._live

    def __iter__(self) -> Iterator:
        lines = list(self._lines.values())
        entries = lines[0] if len(lines) == 1 else heapq.merge(*lines)
        return iter([e[1] for e in entries if e[1] is not None])

    # -------------------------------------------------------------- join
    def append(self, request, expires_at: float = math.inf) -> None:
        self._join(request, expires_at, self._back, left=False)
        self._back += 1

    def appendleft(self, request, expires_at: float = math.inf) -> None:
        self._join(request, expires_at, self._front, left=True)
        self._front -= 1

    def _join(self, request, expires_at: float, place: int,
              left: bool) -> None:
        if request.id in self._live:
            raise ValueError(f"request id {request.id} already queued")
        entry = [place, request, expires_at]
        self._live[request.id] = entry
        line = self._lines.get(request.priority)
        if line is None:
            line = self._lines[request.priority] = deque()
        (line.appendleft if left else line.append)(entry)
        if expires_at < math.inf:
            if len(self._expiries) > 2 * len(self._live) + 64:
                # a seated request's entry leaves the heap only when
                # its time comes: keep it within twice the queue
                self._expiries = [e for e in self._expiries
                                  if e[2][1] is not None]
                heapq.heapify(self._expiries)
            heapq.heappush(self._expiries, (expires_at, place, entry))

    # ------------------------------------------------------------- leave
    def _take(self, priority: int, left: bool):
        """The request at one end of a priority's line, out."""
        line = self._lines[priority]
        return self._leave(line[0 if left else -1], line)

    def _leave(self, entry: list, line: deque):
        """Take an entry's request out and drop what is dead at the
        line's ends (the entry itself, if it stood at one)."""
        request, entry[1] = entry[1], None
        del self._live[request.id]
        while line and line[0][1] is None:
            line.popleft()
            self.examined += 1
        while line and line[-1][1] is None:
            line.pop()
            self.examined += 1
        if not line:
            del self._lines[request.priority]
        return request

    def pop_next(self):
        """Highest priority first; FIFO within a priority."""
        return self._take(max(self._lines), left=True)

    def pop_last(self):
        """The request `pop_next` would serve last: lowest priority,
        youngest within it."""
        return self._take(min(self._lines), left=False)

    def popleft(self):
        """The front of the one line, whatever its priority."""
        return self._take(min(self._lines, key=lambda p:
                              self._lines[p][0][0]), left=True)

    def lowest(self):
        """The first request of the lowest priority, left in place."""
        return self._lines[min(self._lines)][0][1]

    def remove(self, request_id):
        """Take one request out by id, wherever it stands."""
        entry = self._live[request_id]
        return self._leave(entry, self._lines[entry[1].priority])

    def pop_expired(self, now: float) -> list:
        """Every queued request whose expiry time has passed, out of
        the queue, in the line's order."""
        heap, due = self._expiries, []
        while heap:
            self.examined += 1
            if heap[0][0] > now:
                break
            entry = heapq.heappop(heap)[2]
            if entry[1] is not None:
                due.append(entry)
        due.sort(key=lambda e: e[0])
        return [self.remove(e[1].id) for e in due]

    def clear(self) -> None:
        self._lines.clear()
        self._live.clear()
        self._expiries.clear()
