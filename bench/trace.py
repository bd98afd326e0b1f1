"""Reduction of a profiler trace (``.xplane.pb``) to per-layer device times.

Device planes are ``/device:TPU:<n>``; their operations are the events of
the ``XLA Ops`` line, where a loop's event spans the events of its body: an
operation's time is its self time, its duration less that of the events it
holds. The ``Async XLA Ops`` line adds the asynchronous collectives. Each
operation goes to the first layer
(``bench/layers/*.json``, in the order of their ``order`` key) that has a
pattern matching its name or its HLO category; the layer with
``"default": true`` takes the rest. The host's spans (``bench.*``
annotations of the harness) bound the window and name what the host was
doing in each idle gap of the device.

    python bench/trace.py <file.xplane.pb>    # what a trace holds, by hand
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_SPAN = "bench."
TOP = 10


@dataclasses.dataclass
class Layer:
    key: str
    layer: str
    order: int
    patterns: list
    default: bool = False
    collective: bool = False

    def matches(self, text: str) -> bool:
        return any(p.search(text) for p in self.patterns)


def load_layers(directory: pathlib.Path) -> list:
    layers = []
    for f in sorted(pathlib.Path(directory).glob("*.json")):
        d = json.loads(f.read_text())
        layers.append(Layer(key=f.stem, layer=d["layer"], order=int(d["order"]),
                            patterns=[re.compile(p) for p in d.get("patterns", [])],
                            default=bool(d.get("default", False)),
                            collective=bool(d.get("collective", False))))
    return sorted(layers, key=lambda x: x.order)


def classify(text: str, layers: list) -> Layer:
    for layer in layers:
        if not layer.default and layer.matches(text):
            return layer
    return next(x for x in layers if x.default)


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def uncovered(interval, covered: list) -> float:
    """Length of ``interval`` not inside the merged intervals ``covered``."""
    s, e = interval
    left = e - s
    for cs, ce in covered:
        if ce <= s:
            continue
        if cs >= e:
            break
        left -= min(e, ce) - max(s, cs)
    return left


def op_name(ev) -> str:
    """The HLO instruction's name (``%sparsign_pack2bit_2d.16``) without the
    text of its shapes and operands."""
    return ev.name.split(" = ", 1)[0]


def self_times(events) -> list:
    """(start, end, name, self time) of clipped events, nested ones taken out
    of the event that holds them."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, name, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        out.append(rec)
        stack.append(rec)
    return out


@dataclasses.dataclass
class Reduced:
    steps: int
    devices: int
    window_s: float
    busy_s: float                 # mean over the devices
    layer_s: dict                 # layer key -> device seconds, mean over devices
    exposed_s: dict               # collective layer key -> seconds with no
                                  # other operation running on that device
    breakdown: dict


def device_planes(pd) -> list:
    return sorted((p for p in pd.planes if DEVICE_PLANE.match(p.name)),
                  key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))


def host_spans(pd) -> list:
    spans = []
    for p in pd.planes:
        if p.name.startswith("/host"):
            for line in p.lines:
                spans += [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events
                          if ev.name.startswith(HOST_SPAN)]
    return sorted(spans)


def reduce(path, layers: list, *, steps: int) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans = host_spans(pd)
    if not spans:
        raise SystemExit("bench: the trace holds no host spans of the harness")
    w0, w1 = spans[0][0], max(e for _, e, _ in spans)
    planes = device_planes(pd)
    if not planes:
        raise SystemExit("bench: the trace holds no TPU device plane")
    layer_s = {x.key: 0.0 for x in layers}
    exposed_s = {x.key: 0.0 for x in layers if x.collective}
    busy, op_time, gaps = 0.0, {}, []
    for plane in planes:
        sync, asyn = [], []
        for line in plane.lines:
            if line.name in (OPS_LINE, ASYNC_LINE):
                for ev in line.events:
                    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                    if e > s:
                        (sync if line.name == OPS_LINE else asyn).append(
                            (s, e, op_name(ev)))
        ops = [(s, e, n, t, classify(n, layers)) for s, e, n, t in self_times(sync)]
        for s, e, n in asyn:
            layer = classify(n, layers)
            if layer.collective:
                ops.append((s, e, n, e - s, layer))
        merged = merge([(s, e) for s, e, _ in sync])
        busy += sum(e - s for s, e in merged)
        for s, e, name, t, layer in ops:
            layer_s[layer.key] += t
            op_time[name] = op_time.get(name, 0.0) + t
        for layer in layers:
            if layer.collective:
                others = merge([(s, e) for s, e, _, t, x in ops
                                if x is not layer and t > 0])
                exposed_s[layer.key] += sum(
                    uncovered((s, e), others) for s, e, _, _, x in ops if x is layer)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(planes)
    scale = 1e-9 / n
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    breakdown = {
        "device_ops": [[k, v * scale] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[host_doing(g, spans), (g[1] - g[0]) * 1e-9] for g in top_gaps],
    }
    return Reduced(steps=steps, devices=n, window_s=(w1 - w0) * 1e-9,
                   busy_s=busy * scale,
                   layer_s={k: v * scale for k, v in layer_s.items()},
                   exposed_s={k: v * scale for k, v in exposed_s.items()},
                   breakdown=breakdown)


def host_doing(gap, spans) -> str:
    """The host span that overlaps the gap most, or 'host: no span'."""
    best, name = 0.0, "host: no span"
    for s, e, n in spans:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > best:
            best, name = o, n
    return name


def dump(path) -> None:
    """Planes, lines and the operations that took most time, for a look by hand."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    for p in pd.planes:
        print(f"plane {p.name!r}")
        for line in p.lines:
            evs = list(line.events)
            tot = {}
            for ev in evs:
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"{sum(tot.values()) * 1e-6:.3f} ms")
            if DEVICE_PLANE.match(p.name) or line.name.startswith("python") is False:
                for name, t in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                    ex = next(ev for ev in evs if ev.name == name)
                    stats = {k: (str(v)[:160]) for k, v in dict(ex.stats).items()}
                    print(f"    {t * 1e-6:10.3f} ms  {name!r}  "
                          f"t0={ex.start_ns:.0f} {stats}")


if __name__ == "__main__":
    dump(sys.argv[1])
