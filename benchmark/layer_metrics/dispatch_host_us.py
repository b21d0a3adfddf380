"""Host microseconds per chunk inside the program's dispatch layer: the
mean duration of the window's top-level program spans
(``tpucfd.dispatch``, ``dispatch_spans.chunks``). Nothing to read on a
trace whose program opens no such span."""

from benchmark import dispatch_spans


def read(ctx):
    if ctx.trace is None:
        return None
    chunks = dispatch_spans.chunks(ctx.trace)
    if not chunks:
        return None
    return 1e6 * sum(e - s for s, e in chunks) / len(chunks)
