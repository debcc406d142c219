"""From a JAX profiler trace to device times.

A run with ``--trace 1`` records the measured window with
``jax.profiler``. ``load`` keeps what the reduction needs: each device
plane's ``XLA Ops`` line (events named by the HLO instruction they ran,
``%flash_fwd.16 = ...``) and the host spans the harness opened
(``chipbench.window``, ``.batch``, ``.dispatch``, ``.block``), with start
and duration in nanoseconds. Device and host clocks agree to about a
millisecond.

``summarize`` reduces that to per-chip means:

* busy: the union of the device's op intervals inside the window;
* kernel time and calls: the ops whose instruction the compiled program
  names as that kernel's ``tpu_custom_call``;
* collective time, and its exposed part: leaf collective ops (all-gather,
  all-reduce, reduce-scatter, collective-permute, all-to-all, their
  ``-start``/``-done`` halves) during which no other leaf op runs;
* the ops that took most self time (an op's time less that of the ops
  nested in it, as a ``while`` holds its body), and, on the first chip,
  the longest idle gaps, each named by the host span it overlaps most."""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re

import jax

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OP = re.compile(r"^%([A-Za-z0-9_.\-]+) =")
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)")
HOST_PREFIX = "chipbench."


@contextlib.contextmanager
def recording(directory: str):
    jax.profiler.start_trace(directory)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def op_name(event_name: str) -> str:
    m = OP.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def base_name(op: str) -> str:
    """``flash_fwd.16`` → ``flash_fwd``."""
    return re.sub(r"(\.\d+)+$", "", op)


def load(directory: str) -> dict:
    """{"devices": {index: [[op, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]} from the trace file."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    pd = ProfileData.from_file(files[0])
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                devices.setdefault(int(m.group(1)), []).extend(
                    [op_name(e.name), e.start_ns, e.duration_ns]
                    for e in line.events)
            elif plane.name == "/host:CPU":
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def kernel_ops(hlo_text: str, kernels) -> dict:
    """{instruction name: kernel} for each ``tpu_custom_call`` the compiled
    program names after one of ``kernels``."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT )?%([A-Za-z0-9_.\-]+) =", line)
        if m and base_name(m.group(1)) in kernels:
            out[m.group(1)] = base_name(m.group(1))
    return out


def merged(intervals) -> list:
    """The union of ``intervals`` as disjoint [start, end], in order."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> float:
    return sum(e - s for s, e in merged(intervals))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract_ns(a, b) -> float:
    """Measure of the union of ``a`` less the union of ``b``."""
    return union_ns(a + b) - union_ns(b)


def nesting(ops):
    """(self time, is leaf) of each op [name, start, dur], in order."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_t = [float(op[2]) for op in ops]
    leaf = [True] * len(ops)
    stack = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        for parent in reversed(stack):   # the innermost op holding this one
            if e <= ops[parent][1] + ops[parent][2]:
                self_t[parent] -= ops[i][2]
                leaf[parent] = False
                break
        stack.append(i)
    return self_t, leaf


@dataclasses.dataclass
class Summary:
    chips: int
    window_s: float
    busy_s: float                 # per-chip mean
    kernel_s: dict                # kernel → per-chip mean seconds
    kernel_calls: dict            # kernel → per-chip mean calls
    collective_s: float
    exposed_collective_s: float
    device_ops: list              # [[op, self seconds per chip], ...]
    idle_gaps: list               # [[host span, seconds], ...], chip 0


def summarize(events: dict, kernels: dict, top: int = 10) -> Summary:
    """``kernels``: {instruction name: kernel} (``kernel_ops``)."""
    win = [h for h in events["host"] if h[0] == HOST_PREFIX + "window"]
    if len(win) != 1:
        raise RuntimeError(f"expected one window span, found {len(win)}")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    devs = sorted(events["devices"])
    if not devs:
        raise RuntimeError("no device ran an operation in the window")
    busy = coll = exposed = 0.0
    k_time = {k: 0.0 for k in set(kernels.values())}
    k_calls = {k: 0 for k in set(kernels.values())}
    self_by_op: dict = {}
    gaps = []
    for d in devs:
        ops = [op for op in events["devices"][d]
               if op[1] + op[2] > w0 and op[1] < w1]
        self_t, leaf = nesting(ops)
        iv = clip([(s, s + t) for _, s, t in ops], w0, w1)
        busy += union_ns(iv)
        c_iv, o_iv = [], []
        for op, is_leaf, st in zip(ops, leaf, self_t):
            name, s, t = op
            if name in kernels:
                k_time[kernels[name]] += t
                k_calls[kernels[name]] += 1
            if is_leaf:
                (c_iv if COLLECTIVE.match(name) else o_iv).append((s, s + t))
            key = kernels.get(name, base_name(name))
            self_by_op[key] = self_by_op.get(key, 0.0) + st
        c_iv, o_iv = clip(c_iv, w0, w1), clip(o_iv, w0, w1)
        coll += union_ns(c_iv)
        exposed += subtract_ns(c_iv, o_iv)
        if d == devs[0]:
            gaps = _gaps(iv, w0, w1, events["host"], top)
    n = len(devs)
    ops_top = sorted(self_by_op.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        chips=n, window_s=(w1 - w0) / 1e9, busy_s=busy / n / 1e9,
        kernel_s={k: v / n / 1e9 for k, v in k_time.items()},
        kernel_calls={k: v / n for k, v in k_calls.items()},
        collective_s=coll / n / 1e9, exposed_collective_s=exposed / n / 1e9,
        device_ops=[[k, v / n / 1e9] for k, v in ops_top],
        idle_gaps=gaps)


def _gaps(busy_iv, w0, w1, host, top) -> list:
    """The ``top`` longest idle stretches of one chip inside the window,
    each named by the host span (other than the window) that overlaps it
    most."""
    edges = [w0] + [x for iv in merged(busy_iv) for x in iv] + [w1]
    spans = [(h[0][len(HOST_PREFIX):], h[1], h[1] + h[2]) for h in host
             if h[0] != HOST_PREFIX + "window"]
    idle = sorted(((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s),
                  key=lambda g: g[0] - g[1])[:top]
    out = []
    for s, e in idle:
        best, where = 0.0, "other"
        for name, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, where = ov, name
        out.append([where, (e - s) / 1e9])
    return out
