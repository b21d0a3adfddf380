"""The program's dispatch layer in a traced window, read from its host
spans alone (the ``(start, end, name)`` spans of :func:`trace.read`).

A chunk is one top-level program span (``tpucfd.dispatch``, which the
program opens first in each public driver call and closes on return)
that starts in the window; a span nested in another is not a chunk. Its
``tpucfd.dispatch.execute`` child is the call of the compiled program,
which returns once the runtime has put the program in the chips' queues.

Every time here is on the host's clock. The device planes' clock on a
v5e reads 0.4-1.9 ms off the host's, by an amount that changes from run
to run, so nothing here subtracts a device time from a host time. A
trace of a program that opens no such span has no chunks.
"""

from __future__ import annotations

TOP_SPAN = "tpucfd.dispatch"
EXECUTE_SPAN = "tpucfd.dispatch.execute"


def chunks(trace) -> list:
    """``(start, end)`` of each outermost ``tpucfd.dispatch`` span that
    starts in the window, in order."""
    lo, hi = trace.window
    out = []
    for s, e, n in trace.host:  # sorted by start
        if n == TOP_SPAN and lo <= s < hi and not (out and s < out[-1][1]):
            out.append((s, e))
    return out


def enqueued(trace) -> list:
    """For each chunk (:func:`chunks`), the end of its first
    ``tpucfd.dispatch.execute`` child: when the chunk's first program
    was in the queue. ``None`` for a chunk without one."""
    spans = chunks(trace)
    out = [None] * len(spans)
    i = 0
    for s, e, n in trace.host:
        if n != EXECUTE_SPAN:
            continue
        while i < len(spans) and spans[i][1] < s:
            i += 1
        if i == len(spans):
            break
        if spans[i][0] <= s and e <= spans[i][1] and out[i] is None:
            out[i] = e
    return out
