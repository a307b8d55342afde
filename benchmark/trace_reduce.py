"""From a profiler trace (``.xplane.pb``) to the few numbers the per-layer
metrics read. Kept with the benchmark so that every PR computes them the
same way; checked on the recorded trace in ``testdata/``.

What it gives (``reduce``):

- ``busy_s``: seconds in which an operation ran on the device, the union of
  the intervals of the device's op line, averaged over the devices;
  ``window_s``: the traced window, the ``bench.window`` host span where the
  trace has one, else first op start to last op end.
- ``op_self_s``: device 0's seconds by operation name, *self* time (an op
  that holds others, a ``while`` round a scanned layer, is charged only what
  its children leave), so the sums add up to the busy time.
- ``module_s``: device 0's seconds by XLA program name.
- ``matching(text)``: self seconds of the ops whose name or string stats
  (``tf_op``/``long_name``: kernel names, ``named_scope``s) hold ``text``.
- ``gaps``: device 0's idle seconds inside the window by the ``bench.*``
  host span open at the middle of each gap (``none`` where none was).

Nothing per mesh axis, per stage or per request: that needs names only the
program can give (the ``tracing`` issue).
"""

import collections
import glob
import os
import sys

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter")


def find_xplane(trace_dir):
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    """The trace as plain data: ``{plane: {line: [(name, start_ns, dur_ns,
    text)]}}`` where ``name`` is the short name and ``text`` joins the
    event's full name and its string stats. Stats are read once per
    distinct event name (they repeat with it)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        texts = {}
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                full = ev.name
                if full not in texts:
                    texts[full] = (short_name(full), full + " " + " ".join(
                        str(v) for _, v in ev.stats if isinstance(v, str)))
                events.append(
                    (texts[full][0], int(ev.start_ns), int(ev.duration_ns),
                     texts[full][1]))
    return planes


def short_name(full):
    """A device op's event name is its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``): keep the instruction's name."""
    return full.split(" = ", 1)[0].lstrip("%")[:80]


def device_planes(planes):
    names = [p for p in planes if p.startswith("/device:TPU:")
             and OP_LINE in planes[p]]
    return sorted(names, key=lambda p: int(p.rsplit(":", 1)[1].split()[0]))


def union(intervals):
    """Merged ``[start, end)`` intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events):
    """``{name: ns}`` and ``{name: text}`` with each event charged its
    duration less what the events nested inside it cover."""
    totals = collections.Counter()
    texts = {}
    stack = []          # [end, name, self_ns]
    for name, start, dur, text in sorted(events, key=lambda e: (e[1], -e[2])):
        texts[name] = text
        end = start + dur
        while stack and stack[-1][0] <= start:
            done = stack.pop()
            totals[done[1]] += done[2]
        if stack:
            stack[-1][2] -= min(dur, stack[-1][0] - start)
        stack.append([end, name, dur])
    for done in stack:
        totals[done[1]] += done[2]
    return totals, texts


def host_spans(planes):
    """Every ``bench.*`` span of the host planes: ``(name, start, end)``."""
    spans = []
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            continue
        for events in lines.values():
            for name, start, dur, _ in events:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, start, start + dur))
    return spans


def attribute_gaps(busy, window, spans):
    """Idle ns inside ``window`` by the innermost span open at the middle
    of each gap."""
    gaps = collections.Counter()
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    for a, b in zip(edges[0::2], edges[1::2]):
        a, b = max(a, window[0]), min(b, window[1])
        if b <= a:
            continue
        mid = (a + b) // 2
        open_now = [s for s in inner if s[1] <= mid < s[2]]
        name = min(open_now, key=lambda s: s[2] - s[1])[0] if open_now \
            else "none"
        gaps[name] += b - a
    return gaps


class Reduced(dict):
    def matching(self, text):
        """Self seconds on device 0 of ops whose name or stats hold
        ``text``."""
        return sum(s for name, s in self["op_self_s"].items()
                   if text in name or text in self["op_text"].get(name, ""))

    def collective_s(self):
        return sum(s for name, s in self["op_self_s"].items()
                   if name.startswith(COLLECTIVES))


def reduce(path, n_devices=None):
    planes = load(path) if isinstance(path, str) else path
    devices = device_planes(planes)
    if n_devices is not None:
        devices = devices[:n_devices]
    if not devices:
        raise ValueError("the trace holds no device plane with an op line")
    spans = host_spans(planes)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    busy_by_device = []
    for dev in devices:
        ops = planes[dev][OP_LINE]
        busy_by_device.append(union((s, s + d) for _, s, d, _ in ops))
    if windows:
        window = (windows[0][1], windows[0][2])
    else:
        window = (min(b[0][0] for b in busy_by_device if b),
                  max(b[-1][1] for b in busy_by_device if b))
    busy_ns = [
        sum(min(e, window[1]) - max(s, window[0]) for s, e in b
            if e > window[0] and s < window[1])
        for b in busy_by_device
    ]
    self_ns, texts = self_times(planes[devices[0]][OP_LINE])
    modules = collections.Counter()
    for name, _, dur, _ in planes[devices[0]].get(MODULE_LINE, []):
        modules[name] += dur
    gaps = attribute_gaps(busy_by_device[0], window, spans)
    op_self_s = {k: v / 1e9 for k, v in self_ns.items()}
    return Reduced(
        busy_s=sum(busy_ns) / len(busy_ns) / 1e9,
        busy_s_by_device=[b / 1e9 for b in busy_ns],
        window_s=(window[1] - window[0]) / 1e9,
        devices=devices,
        op_self_s=op_self_s, op_text=texts,
        module_s={k: v / 1e9 for k, v in modules.items()},
        gaps_s={k: v / 1e9 for k, v in gaps.items()},
        top_ops=[[k, v] for k, v in sorted(
            op_self_s.items(), key=lambda kv: -kv[1])[:10]],
        top_gaps=[[k, v / 1e9] for k, v in gaps.most_common(10)],
    )


def dump(path, per_line=4):
    """What a trace holds, for reading one by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events")
            for ev in events[:per_line]:
                print("    ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      [(k, str(v)[:120]) for k, v in ev.stats])


if __name__ == "__main__":
    dump(sys.argv[1])
