"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists; every other function works on those lists, so the arithmetic can be
checked against hand counts (``tests/bench/test_bench_trace.py``).

* device ops: the events of each device plane's ``XLA Ops`` line, as
  ``(name, start_ns, end_ns)`` per chip, named by their HLO instruction
  without its numeric suffix (``site_step_linear``, ``copy_bitcast_fusion``);
  a collective whose instruction name hides it gets its opcode appended;
* markers: the ``XLA Modules`` events of the benchmark's own marker program
  (``bench_window_marker``), run once as the traced window opens and once
  as it closes; they bound the window on the device clock.

Host tracing stays off: the host-to-device copy of a Γ segment emits one
``Transpose`` event per tile (about 17,000 per MB), which at χ = 10⁴ runs a
traced process out of host memory.  The benchmark's own host spans are kept
on the host clock instead and moved onto the device clock by the first
marker (``harness``).
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKER = "bench_window_marker"
COLLECTIVE = re.compile(r"all-reduce|reduce-scatter|all-gather|all-to-all|"
                        r"collective-permute")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def op_name(text: str) -> str:
    """``%site_step_linear.6 = (f32[..]) custom-call(...)`` → the
    instruction name without its suffix, with a hidden collective's opcode
    appended."""
    head, eq, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    opcode = re.search(r"\s([a-z][a-z0-9_-]*)\(", rest) if eq else None
    if opcode and COLLECTIVE.search(opcode.group(1)) \
            and not COLLECTIVE.search(name):
        name = f"{name}:{opcode.group(1)}"
    return name


def load(path: str) -> tuple[dict, list]:
    """(device ops by chip index, marker intervals) from one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, markers = {}, []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        ops = devices.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops.extend((op_name(ev.name), int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns))
                           for ev in line.events)
            elif line.name == MODULES_LINE:
                markers.extend((int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns))
                               for ev in line.events if MARKER in ev.name)
    return devices, sorted(markers)


def window(markers: list) -> tuple[int, int]:
    """The traced window: from the first marker's start to the last's end
    (the same two markers run on every chip the marker program spans)."""
    if len(markers) < 2:
        raise RuntimeError(f"expected the window's two {MARKER} programs, "
                           f"found {len(markers)}")
    return markers[0][0], max(e for _, e in markers)


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def busy_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi] during which some op runs on the chip."""
    return length(union([(s, e) for _, s, e in ops], lo, hi))


def subtract(a, b) -> list[tuple[int, int]]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def collective_exposed_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi] in which a collective runs on the chip and no
    other op does."""
    coll = union([(s, e) for n, s, e in ops if COLLECTIVE.search(n)], lo, hi)
    comp = union([(s, e) for n, s, e in ops if not COLLECTIVE.search(n)],
                 lo, hi)
    return length(subtract(coll, comp))


def kernel_events(ops, pattern: str, lo: int, hi: int) -> list[int]:
    """Durations (ns) of the ops whose name matches ``pattern`` and that
    start inside [lo, hi]."""
    rx = re.compile(pattern)
    return [e - s for n, s, e in ops if rx.search(n) and lo <= s < hi]


def top_ops(devices: dict, lo: int, hi: int, k: int = 10) -> list:
    """[name, seconds per chip] of the ops that took most device time."""
    tot: dict[str, int] = {}
    for ops in devices.values():
        for n, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                tot[n] = tot.get(n, 0) + e - s
    chips = max(1, len(devices))
    return [[n, t / chips / 1e9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(ops, host, lo: int, hi: int, k: int = 10) -> list:
    """[label, seconds] of the chip's ``k`` longest idle gaps in [lo, hi],
    each labelled by the innermost ``bench.*`` host span that covers its
    middle (``idle`` when none does)."""
    gaps = subtract([(lo, hi)], union([(s, e) for _, s, e in ops], lo, hi))
    spans = [(n, s, e) for n, s, e in host if n.startswith("bench.")]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) // 2
        cover = [(e2 - s2, n) for n, s2, e2 in spans if s2 <= mid < e2]
        out.append([min(cover)[1] if cover else "idle", (e - s) / 1e9])
    return out
