"""The program's own spans in a profiler trace, reduced against the device.

The program puts each phase of a served flush on the profiler's timeline
as a ``TraceAnnotation`` named ``repro/<phase>`` (``repro.obs.trace``):
``flush`` around the whole host side, and inside it ``flush_assemble``,
``dispatch`` (``stage_rows``, ``stage_h2d``, ``device`` with ``launch``
and ``fetch``, ``stage_rezero``), ``validate`` and ``resolve``; a compile
is a ``compile`` span. ``load`` reads them from the same ``.xplane.pb`` as
``chipbench.trace.load``, on the same clock as the device ops; the rest is
arithmetic on plain lists, so tests can feed it a synthetic trace.

* A phase's time is the summed duration of its spans inside the window.
* Idle time in a flush is the device's idle time inside the union of the
  ``flush`` spans: the part of the idle share that the flush path holds
  the device back for, as against waiting for rows or a deadline.
* An idle gap is labelled by the innermost program span that covers most
  of it ("no program span" where none does), which names the phase a
  stall falls in.
"""
import glob
import os

from chipbench import trace

PROGRAM_PREFIX = "repro/"  # the program's repro.obs.trace.TIMELINE_PREFIX
FLUSH = "flush"
STAGING = ("stage_rows", "stage_h2d", "stage_rezero")
NO_SPAN = "no program span"


def load(log_dir: str) -> list:
    """[(name, t0_ns, t1_ns)] of the program's spans on every host plane of
    the newest ``.xplane.pb`` under ``log_dir``, prefix taken off."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append((ev.name[len(PROGRAM_PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def phase_ns(spans, names, lo, hi) -> float:
    """Summed time of the spans named in ``names``, clipped to [lo, hi)."""
    return float(sum(min(b, hi) - max(a, lo) for n, a, b in spans
                     if n in names and b > lo and a < hi))


def idle(events, lo, hi) -> list:
    """The device's idle intervals in [lo, hi): the window minus the union
    of the device ops ``events`` [(name, t0, t1)]."""
    out, t = [], lo
    for a, b in trace.union([(max(a, lo), min(b, hi)) for _, a, b in events
                             if b > lo and a < hi]):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def overlap_ns(xs, ys) -> float:
    """Length of the intersection of two interval lists."""
    xs, ys = trace.union(xs), trace.union(ys)
    total, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return float(total)


def idle_in_ns(events, spans, lo, hi, name: str = FLUSH) -> float:
    """Device idle time in [lo, hi) that falls inside a span ``name``."""
    return overlap_ns(idle(events, lo, hi),
                      [(a, b) for n, a, b in spans if n == name])


def label(spans, a, b) -> str:
    """The innermost (shortest) span covering more than half of [a, b)."""
    best = None
    for n, s, e in spans:
        if 2 * (min(b, e) - max(a, s)) > b - a and (
                best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else NO_SPAN


def labelled_gaps(events, spans, lo, hi, k: int = 10) -> list:
    """[[label, seconds]] of the ``k`` longest device idle gaps in
    [lo, hi), each labelled by ``label``."""
    gaps = sorted(idle(events, lo, hi), key=lambda g: g[0] - g[1])[:k]
    return [[label(spans, a, b), (b - a) * 1e-9] for a, b in gaps]


def self_share(spans, lo, hi, name: str = FLUSH) -> float:
    """Share of the time of the spans ``name`` lying wholly in [lo, hi)
    that no span nested in them covers; None when there are none."""
    parents = [(a, b) for n, a, b in spans if n == name and lo <= a
               and b <= hi]
    total = sum(b - a for a, b in parents)
    if not total:
        return None
    covered = overlap_ns(parents, [(a, b) for n, a, b in spans if n != name])
    return 1.0 - covered / total


def reduce(events, spans, lo, hi) -> dict:
    """What the per-flush phase readings need from one traced window:
    ``events`` are one chip's device ops, ``spans`` the program's spans.
    None when the window holds no program span."""
    inside = [s for s in spans if s[2] > lo and s[1] < hi]
    if not inside:
        return None
    names = sorted({n for n, _, _ in inside})
    return {
        "window_s": (hi - lo) * 1e-9,
        "phase_s": {n: phase_ns(inside, {n}, lo, hi) * 1e-9 for n in names},
        "count": {n: sum(s[0] == n for s in inside) for n in names},
        "idle_in_flush_s": idle_in_ns(events, inside, lo, hi) * 1e-9,
        "flush_self_share": self_share(inside, lo, hi),
        "idle_gaps": labelled_gaps(events, inside, lo, hi),
    }


def stage_ms_per_flush(red, flushes):
    """Host staging per flush: row copy, host-to-device call, re-zeroing."""
    if red is None or not flushes:
        return None
    return 1e3 * sum(red["phase_s"].get(n, 0.0) for n in STAGING) / flushes


def fetch_ms_per_flush(red, flushes):
    """The wait for a flush's outputs, per flush."""
    if red is None or not flushes:
        return None
    return 1e3 * red["phase_s"].get("fetch", 0.0) / flushes


def idle_in_flush(red):
    """Device idle time inside ``flush`` spans, in % of the window."""
    if red is None or not red["window_s"]:
        return None
    return 100.0 * red["idle_in_flush_s"] / red["window_s"]
