"""Reduction of one profiler trace to device metrics.

``record`` starts and stops JAX's profiler around a sub-window of a run;
``load`` reads the ``.xplane.pb`` it wrote into plain lists; everything
else is arithmetic on those lists, so tests can feed it a synthetic trace.

* Device ops are the events of the ``XLA Ops`` line of every TPU plane.
  Busy time is the union of their intervals inside the traced window,
  averaged over the chips used; the idle share is 1 minus busy over the
  window.
* Host spans are the ``TraceAnnotation`` events whose names start with
  ``HOST_PREFIX``, which the harness puts around its own calls into the
  program. An idle gap on the device is labelled by the host span that
  covers most of it ("no host span" where none does).
* A kernel class's time is the summed duration of the device ops charged
  to it: its kernels, whose names hold one of the class's kernel names,
  and every op between a kernel's producer and the kernel, such as the
  im2col slices and reshapes in front of a ``qmatmul``. An op is charged
  to a class when all the kernels it feeds, through ops that are no
  kernel, belong to that class; an op that feeds none (after the last
  kernel) or kernels of two classes is charged to none. An op's name is
  the left-hand side of its HLO text (``qmatmul.15``): the Pallas kernel's
  name for a kernel, XLA's op name otherwise. Which op feeds which is read
  from the executables' HLO text (``hlo_inputs``), since ops that take no
  device time, such as a bitcast, are not in the trace. The bucket
  executables of one model reuse op names at other batch sizes, so an op
  is known by its name and the first array type of its result
  (``slice.15 s8[64,48,48,128]``), in the trace as in the HLO text.
"""
import glob
import os
import re

HOST_PREFIX = "chipbench:"
WINDOW_SPAN = "traced"  # the host span that marks the traced window
DEVICE_LINE = "XLA Ops"


def record(log_dir: str):
    """Start the profiler into ``log_dir``; returns its stop function.
    The Python tracer stays off: it would slow the host path it measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    return jax.profiler.stop_trace


def load(log_dir: str) -> dict:
    """{"device": {plane: [(name, t0_ns, t1_ns)]}, "host": [(name,
    t0_ns, t1_ns)]} from the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for ev in line.events:
                    # the name is the HLO instruction's text
                    name, _, rhs = ev.name.partition(" = ")
                    evs.append((op_key(name.lstrip("%"), rhs),
                                ev.start_ns, ev.start_ns + ev.duration_ns))
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name[len(HOST_PREFIX):], ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return {"device": device, "host": host}


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", re.M)
_OPERAND = re.compile(r"%([\w.\-]+)")
_ARRAY_TYPE = re.compile(r"\w+\[[\d,]*\]")


def op_key(name: str, rhs: str) -> str:
    """How an op is known: its name and the first array type of its result,
    from the right-hand side ``rhs`` of its HLO text."""
    m = _ARRAY_TYPE.search(rhs)
    return f"{name} {m.group(0)}" if m else name


def hlo_inputs(texts) -> dict:
    """{op: [operands]} of the HLO modules ``texts`` (each an executable's
    ``as_text()``), ops known by ``op_key``."""
    out: dict = {}
    for text in texts:
        ops = _INSTRUCTION.findall(text)
        key = {name: op_key(name, rhs) for name, rhs in ops}
        for name, rhs in ops:
            out.setdefault(key[name], set()).update(
                key[o] for o in _OPERAND.findall(rhs) if o in key)
    return {k: sorted(v) for k, v in out.items()}


def charges(names, inputs: dict, classes: dict) -> dict:
    """{op name: kernel class} of the ops ``names`` charged to a class (see
    the module's docstring). ``inputs`` maps an op to its operands,
    ``classes`` a class to its kernel names."""
    def kernel_class(n):
        for c, kernels in classes.items():
            if any(k in n for k in kernels):
                return c
        return None

    users: dict = {}
    for n, ins in inputs.items():
        for i in ins:
            users.setdefault(i, set()).add(n)
    fed: dict = {}  # op -> classes of the kernels it feeds

    def feeds(n):
        if n not in fed:
            fed[n] = frozenset()  # a cycle adds nothing
            out = set()
            for u in users.get(n, ()):
                c = kernel_class(u)
                out |= {c} if c is not None else feeds(u)
            fed[n] = frozenset(out)
        return fed[n]

    out = {}
    for n in names:
        c = kernel_class(n)
        if c is None and len(feeds(n)) == 1:
            (c,) = feeds(n)
        if c is not None:
            out[n] = c
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def union(intervals) -> list:
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_ns(events, lo, hi) -> float:
    """Nanoseconds in [lo, hi) in which at least one event runs."""
    return float(sum(b - a for a, b in
                     union(_clip([e[1:] for e in events], lo, hi))))


def class_ns(events, names, lo, hi) -> float:
    """Summed device time of the events whose name is in ``names``."""
    total = 0.0
    for name, a, b in events:
        if name in names:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                total += b - a
    return total


def top_ops(events, lo, hi, k: int = 10) -> list:
    """[[name, seconds]] of the ``k`` op names that took most device time."""
    per: dict = {}
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            per[name] = per.get(name, 0.0) + (b - a)
    best = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns * 1e-9] for n, ns in best]


def idle_gaps(events, host, lo, hi, k: int = 10) -> list:
    """[[label, seconds]] of the ``k`` longest device idle gaps in
    [lo, hi), each labelled by the host span covering most of it."""
    busy = union(_clip([e[1:] for e in events], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        cover: dict = {}
        for name, s, e in host:
            if name == WINDOW_SPAN:
                continue
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[name] = cover.get(name, 0) + ov
        label = max(cover, key=cover.get) if cover else "no host span"
        out.append([label, (b - a) * 1e-9])
    return out


def reduce(tr: dict, lo: int, hi: int, classes: dict) -> dict:
    """Device metrics of trace ``tr`` over [lo, hi) ns. ``classes`` maps a
    kernel class to its kernel names. Time is averaged over the chips;
    ``class_ops`` lists each class's ops, the most time first."""
    planes = [evs for evs in tr["device"].values() if evs]
    if not planes:
        return None
    n = len(planes)
    allev = [e for evs in planes for e in evs]
    charge = charges({e[0] for e in allev}, tr.get("inputs", {}), classes)
    mine = {c: {op for op, k in charge.items() if k == c} for c in classes}
    return {
        "busy_s": sum(busy_ns(evs, lo, hi) for evs in planes) / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "class_s": {c: class_ns(allev, mine[c], lo, hi) / n * 1e-9
                    for c in classes},
        "class_ops": {c: top_ops([e for e in allev if e[0] in mine[c]],
                                 lo, hi, k=len(mine[c]))
                      for c in classes},
        "device_ops": top_ops(allev, lo, hi),
        "idle_gaps": idle_gaps(planes[0], tr["host"], lo, hi),
    }
