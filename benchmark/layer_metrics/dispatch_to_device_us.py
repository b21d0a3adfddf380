"""Microseconds from the start of a chunk's top-level program span to the
end of its first ``tpucfd.dispatch.execute`` child, when the call of the
compiled program has returned with the program in the chips' queues;
mean over the chunks that have one (``dispatch_spans.enqueued``). The
program's part of how long the chip waits for a chunk. Both ends are
program spans on the host's clock; the launch after the enqueue lies
on the device's clock and is left out. Nothing to read on a trace whose
program opens no such spans."""

from benchmark import dispatch_spans


def read(ctx):
    if ctx.trace is None:
        return None
    waits = [q - s for (s, _), q in zip(dispatch_spans.chunks(ctx.trace),
                                        dispatch_spans.enqueued(ctx.trace))
             if q is not None]
    if not waits:
        return None
    return 1e6 * sum(waits) / len(waits)
