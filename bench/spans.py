"""The program's own spans, placed on the device clock of a traced run.

The program records its spans (``repro.obs.trace``) on the host's
``perf_counter_ns`` clock; this is the one file of the benchmark that reads
them.  The harness has already moved its own spans onto the device clock
(``ctx.host``).  Each ``service.stream_wait`` span of batch b (inside
``JobHandle.stream()``, from the generator's resume to its yield) lies
inside the harness's ``bench.wait_batch`` span for b (around ``next()``), so

    shift = bench_end − program_end

taken from the pair whose durations differ least places every program span
on the device clock, and that difference bounds the error.

Every function returns None, and never raises, where the spans cannot be
placed: a program that records none, no pair, a bound above
``BOUND_NS``, or a ring that dropped spans the traced window may need.
"""
from __future__ import annotations

from bench import trace as TR

BOUND_NS = 5_000_000            # the idle gaps this explains are 0.4–3.7 s
BENCH_WAIT = "bench.wait_batch"
STREAM_WAIT = "service.stream_wait"
#: spans that cover a whole batch on the consumer's thread, left out when
#: device idle time is put down to what the program was doing
CONSUMER = (STREAM_WAIT,)


def recorded():
    """(spans, dropped) from the program's recorder, or None when the
    program has no recorder."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    return trace.spans(), trace.dropped()


def _last_job(spans, name: str):
    """The ``job`` attribute of the span called ``name`` that closed
    last (the ring keeps spans in the order they close)."""
    last = [r for r in spans if r.name == name]
    return last[-1].attrs.get("job") if last else None


def align(host, spans, dropped: int, lo: int):
    """(shift_ns, bound_ns) from program clock to the device clock of
    ``host`` (the harness's spans), or None."""
    waits = [(s, e) for n, s, e in host if n == BENCH_WAIT]
    job = _last_job(spans, STREAM_WAIT)
    mine = sorted((r for r in spans if r.name == STREAM_WAIT
                   and r.attrs.get("job") == job), key=lambda r: r.start_ns)
    if not waits or len(waits) != len(mine):
        return None
    pairs = [((we - ws) - (r.end_ns - r.start_ns), we - r.end_ns)
             for (ws, we), r in zip(waits, mine)
             if (we - ws) >= (r.end_ns - r.start_ns)]
    if not pairs:
        return None
    bound, shift = min(pairs)
    if bound > BOUND_NS:
        return None
    # the ring drops its oldest spans first: all of them closed before the
    # oldest kept one did
    if dropped and spans and spans[0].end_ns + shift >= lo:
        return None
    return shift, bound


def on_device(ctx, got=None):
    """The program's spans as ``(name, start_ns, end_ns, span_id,
    parent_id, attrs)`` on ``ctx``'s device clock, or None."""
    got = recorded() if got is None else got
    if got is None:
        return None
    spans, dropped = got
    a = align(ctx.host, spans, dropped, ctx.lo)
    if a is None:
        return None
    shift = a[0]
    return [(r.name, r.start_ns + shift, r.end_ns + shift, r.span_id,
             r.parent_id, r.attrs) for r in spans]


def named(spans, name: str, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged intervals of the spans called ``name``, clipped to
    [lo, hi]."""
    return TR.union([(s, e) for n, s, e, *_ in spans if n == name], lo, hi)


def idle_ns(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    """The chip's idle intervals in [lo, hi]."""
    return TR.subtract([(lo, hi)], TR.union([(s, e) for _, s, e in ops],
                                            lo, hi))


def before_timed_job(spans, dropped: int, name: str):
    """Seconds of the spans called ``name`` that end before the timed
    job's first ``service.batch`` begins (the timed job is the job of the
    ``service.batch`` that closed last), or None."""
    job = _last_job(spans, "service.batch")
    if job is None or dropped:
        return None
    t0 = min(r.start_ns for r in spans
             if r.name == "service.batch" and r.attrs.get("job") == job)
    return sum(r.end_ns - r.start_ns for r in spans
               if r.name == name and r.end_ns <= t0) / 1e9


# ---------------------------------------------------------------------------
# Where the time goes (PERF.md §5): not metrics, but the same arithmetic
# ---------------------------------------------------------------------------

def time_by_name(spans, lo: int, hi: int) -> dict[str, float]:
    """Seconds per span name inside [lo, hi] (spans of one name summed,
    not merged: two threads reading at once count twice)."""
    out: dict[str, float] = {}
    for n, s, e, *_ in spans:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[n] = out.get(n, 0.0) + (e - s) / 1e9
    return out


def _depths(spans) -> dict:
    parent = {sid: pid for _, _, _, sid, pid, _ in spans}
    depth: dict = {}
    for sid in parent:
        chain, cur = [], sid
        while cur in parent and cur not in depth:
            chain.append(cur)
            cur = parent[cur]
        base = depth.get(cur, -1)
        for i, c in enumerate(reversed(chain)):
            depth[c] = base + 1 + i
    return depth


def idle_by_span(ops, spans, lo: int, hi: int,
                 skip=CONSUMER) -> dict[str, float]:
    """Seconds of the chip's idle time in [lo, hi] put down to the deepest
    program span open at each instant, on any thread (ties go to the
    shorter span); ``"none"`` where no span is open.  A span's name stands
    for its self time: the part no deeper span covers."""
    depth = _depths(spans)
    spans = [sp for sp in spans
             if sp[0] not in skip and sp[2] > lo and sp[1] < hi]
    out: dict[str, float] = {}
    for a, b in idle_ns(ops, lo, hi):
        cuts = sorted({a, b} | {t for _, s, e, *_ in spans
                                for t in (s, e) if a < t < b})
        for s, e in zip(cuts, cuts[1:]):
            cover = [(-depth[sp[3]], sp[2] - sp[1], sp[0]) for sp in spans
                     if sp[1] <= s and e <= sp[2]]
            name = min(cover)[2] if cover else "none"
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out
