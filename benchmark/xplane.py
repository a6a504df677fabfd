"""From the profiler's `.xplane.pb` to seconds: device busy and idle time,
time per operation, per jitted program and in collectives, and the idle gaps
named by what the host was doing. The arithmetic is on plain
(start, end) intervals in nanoseconds, so `benchmark/checks` can hold it to
a recorded trace.

What a TPU trace looks like (read on the chip, PR 23): one plane per chip
named `/device:TPU:<n>`; its line `XLA Ops` holds one event per executed HLO
operation, nested (a `while` encloses the operations of its body), and its
line `XLA Modules` one event per executed program, named `jit_<function>(<id>)`; `Async XLA Ops` holds the spans of asynchronous
copies and collectives. An operation's event is named by its whole HLO text.
Host threads are lines of the plane `/host:CPU`; a
`jax.profiler.TraceAnnotation` is an event there, on the same clock.
"""

import collections
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv)")
# containers: their time is their children's
CONTROL = re.compile(r"^(while|conditional|call)$")


def union(intervals):
    """Sorted, disjoint intervals covering the same instants."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals):
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes):
    """The parts of `intervals` (disjoint, sorted) outside `holes` (same)."""
    out = []
    holes = list(holes)
    for a, b in intervals:
        cur = a
        for ha, hb in holes:
            if hb <= cur or ha >= b:
                continue
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def overlap(intervals, others):
    return total(intervals) - total(subtract(intervals, others))


def self_times(events):
    """[(name, self ns)] for nested events of one line, given as
    (name, start, end): an event's own time is its duration less the time
    of the events directly inside it."""
    out = []
    stack = []          # [name, end, self]
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


HLO_TEXT = re.compile(r"^%?(\S+) = (.+?) ([a-z][a-z0-9\-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_op(text):
    """(label, opcode) of an `XLA Ops` event. On a TPU the event's name is
    the whole HLO instruction, `%name = type{layout} opcode(operands), ...`.
    The label is what `breakdown` prints and what a reader's `match` sees:
    `name opcode[:custom call target] result type`, layouts dropped. The
    instruction's name changes with any edit to the program; the opcode,
    the target and the result type say what the operation is."""
    found = HLO_TEXT.match(LAYOUT.sub("", text))
    if not found:
        return text[:120], text.split(".")[0].lstrip("%")
    name, result, opcode = found.groups()
    target = TARGET.search(text)
    kind = f"{opcode}:{target.group(1)}" if target else opcode
    return f"{name} {kind} {result}"[:160], opcode


def read_planes(path):
    """The trace as plain data: {plane: {line: [(name, start, end, stats)]}}."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = float(ev.start_ns)
                events.append((ev.name, start, start + float(ev.duration_ns),
                               dict(ev.stats)))
    return planes


def reduce_planes(planes):
    """The summary every trace reader works from. Seconds are averaged over
    the chips that ran anything."""
    host_spans = collections.defaultdict(list)
    for plane, lines in planes.items():
        if DEVICE_PLANE.match(plane):
            continue
        for events in lines.values():
            for name, start, end, _stats in events:
                if name.startswith(HOST_SPAN_PREFIX):
                    host_spans[name].append((start, end))

    device_lines = {p: lines for p, lines in sorted(planes.items())
                    if DEVICE_PLANE.match(p) and lines.get(OPS_LINE)}
    if not device_lines:
        raise ValueError("the trace holds no device operation")
    all_ops = [ev for lines in device_lines.values() for ev in lines[OPS_LINE]]
    if host_spans.get(WINDOW_SPAN):
        lo = min(a for a, _ in host_spans[WINDOW_SPAN])
        hi = max(b for _, b in host_spans[WINDOW_SPAN])
    else:
        lo = min(ev[1] for ev in all_ops)
        hi = max(ev[2] for ev in all_ops)

    n = len(device_lines)
    busy = collective = exposed = 0.0
    ops = collections.Counter()
    op_counts = collections.Counter()
    modules = collections.Counter()
    module_counts = collections.Counter()
    gaps = collections.Counter()
    for lines in device_lines.values():
        events = [parse_op(name) + (max(a, lo), min(b, hi))
                  for name, a, b, _stats in lines[OPS_LINE]
                  if min(b, hi) > max(a, lo)]
        busy_iv = union([(a, b) for _, _, a, b in events])
        busy += total(busy_iv)
        control = {label for label, opcode, _, _ in events
                   if CONTROL.match(opcode)}
        for label, ns in self_times([(label, a, b)
                                     for label, _, a, b in events]):
            if label not in control:
                ops[label] += ns
                op_counts[label] += 1
        # a collective is the synchronous operation itself or, where XLA made
        # it asynchronous, the span from its -start to its -done, which the
        # trace keeps on a line of its own
        asynchronous = [parse_op(name) + (max(a, lo), min(b, hi))
                        for name, a, b, _stats in lines.get(ASYNC_LINE, [])
                        if min(b, hi) > max(a, lo)]
        coll_iv = union([(a, b) for _, opcode, a, b in events + asynchronous
                         if COLLECTIVE.match(opcode)])
        compute_iv = union([(a, b) for label, opcode, a, b in events
                            if not COLLECTIVE.match(opcode)
                            and label not in control])
        collective += total(coll_iv)
        exposed += total(subtract(coll_iv, compute_iv))
        for name, a, b, _stats in lines.get(MODULES_LINE, []):
            if min(b, hi) > max(a, lo):
                key = re.sub(r"\(\d+\)$", "", name)
                modules[key] += min(b, hi) - max(a, lo)
                module_counts[key] += 1
        idle_iv = subtract([(lo, hi)], busy_iv)
        left = idle_iv
        for span, intervals in host_spans.items():
            if span == WINDOW_SPAN:
                continue
            covered = union(clip(intervals, lo, hi))
            gaps[span] += overlap(idle_iv, covered)
            left = subtract(left, covered)
        gaps["host.other"] += total(left)

    def seconds(counter):
        return {k: v / n / 1e9 for k, v in counter.items()}

    return {"devices": n, "window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "collective_s": collective / n / 1e9,
            "collective_exposed_s": exposed / n / 1e9,
            "ops": seconds(ops),
            "op_counts": {k: v / n for k, v in op_counts.items()},
            "modules": seconds(modules),
            "module_counts": {k: v / n for k, v in module_counts.items()},
            "idle_gaps": seconds(gaps)}


def reduce_file(path):
    return reduce_planes(read_planes(path))


def breakdown(summary, top=10):
    """The `breakdown` of a traced run's last line."""
    def longest(table):
        return [[name, secs] for name, secs in
                sorted(table.items(), key=lambda kv: -kv[1])[:top] if secs > 0]
    return {"device_ops": longest(summary["ops"]),
            "idle_gaps": longest(summary["idle_gaps"])}


def matching(table, pattern):
    """Sum of the entries of `table` whose name matches the regex."""
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))


if __name__ == "__main__":
    # python benchmark/xplane.py <file.xplane.pb>: what the trace holds
    planes = read_planes(sys.argv[1])
    for plane, lines in planes.items():
        print("PLANE", plane)
        for line, events in lines.items():
            print("  LINE", line, len(events))
            for name, a, b, stats in events[:int(sys.argv[2]) if len(sys.argv) > 2 else 3]:
                print("     ", name, int(b - a), {k: str(v)[:120] for k, v in stats.items()})
    summary = reduce_planes(planes)
    for key in ("devices", "window_s", "busy_s", "collective_s",
                "collective_exposed_s", "modules", "module_counts", "idle_gaps"):
        print(key, summary[key])
    for name, secs in breakdown(summary, 25)["device_ops"]:
        print(f"{secs:10.6f}  {summary['op_counts'][name]:8.0f}  {name}")
