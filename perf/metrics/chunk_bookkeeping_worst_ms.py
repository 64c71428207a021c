"""Host loop: longest ``fused.bookkeeping`` span (fence to next dispatch in
``train.train``: telemetry, ledger, the row, ``log_fn``, checkpoint,
``stop_fn``) among the chunks that lie wholly inside the window, from the
flight ring: whether a chunk that stood still stood in the host's
bookkeeping. The window's last chunk is left out: the window's end is
stamped inside its span, and what follows the stamp (the profiler's start in
a traced run) is not the window's."""


def bookkeeping_ms(run):
    """``fused.bookkeeping`` span durations (ms) of the window's chunks but
    the last; the spans after them are the traced chunks'. None where the
    program records no such span."""
    try:
        from dist_dqn_tpu import telemetry
    except ImportError:
        return None
    spans = [e["dur_s"] for e in telemetry.get_flight().tail()
             if e["kind"] == "span" and e["name"] == "fused.bookkeeping"]
    n = len(run["series"]["cycle_s"])
    end = len(spans) - run["traced_chunks"]
    if n < 2 or end < n:
        return None
    return [1e3 * s for s in spans[end - n:end - 1]]


def read(run, trace):
    values = bookkeeping_ms(run)
    return max(values) if values else None
