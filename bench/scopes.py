"""Per-layer device time of a traced window, read by the program's scopes.

The trainer traces each layer of its step under a ``jax.named_scope``
(``fwd_bwd``, ``uplink``, ``exchange``, ``server``, ``counters``), and every
operation keeps the name stack it was traced under (its ``op_name``). An
operation's layer is the innermost of these five names in its stack; a name
may sit inside transformation wrappers (``transpose(jvp(fwd_bwd))``). An
operation under none of them is ``unscoped``.

The name stack of an operation of the trace's ``XLA Ops`` line is looked up
by its instruction name in the HLO of the train step (the program
``jit_train_step``) that the trace's ``/host:metadata`` plane holds; where
the trace holds none, the event's own ``tf_op`` stat is taken. Device time is self time (``trace.self_times``), so the layers
and ``unscoped`` add up to the busy time. ``exchange`` also spans the
``Async XLA Ops`` line: its time is the union of its operations' intervals,
and its exposed time the part of that union in which no operation of
another layer runs on the device.

The window is the span of the harness's ``bench.*`` host spans, as in
``trace.reduce``. A trace whose operations carry none of the five names (a
program without the scopes) reduces to nothing, and the readers report
nothing.

    python3 bench/scopes.py <file.xplane.pb>    # the reduction, by hand
"""

from __future__ import annotations

import dataclasses
import functools
import pathlib
import re
import sys

SCOPES = ("fwd_bwd", "uplink", "exchange", "server", "counters")
UNSCOPED = "unscoped"
EXCHANGE = "exchange"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
STEP_PROGRAM = "jit_train_step("
WRAPPED = re.compile(r"^(?:[\w.-]*\()*([^()]*)\)*$")


def layer_of(op_name: str) -> str:
    """The innermost of the five scopes in a name stack, or 'unscoped'."""
    layer = UNSCOPED
    for part in op_name.split("/"):
        m = WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            layer = m.group(1)
    return layer


@dataclasses.dataclass
class Scoped:
    steps: int
    devices: int
    window_s: float
    busy_s: float        # mean over the devices
    layer_s: dict        # scope or 'unscoped' -> self seconds, mean over devices
    exchange_s: float    # union of the exchange's sync and async intervals
    exchange_exposed_s: float

    def ms(self, key: str) -> float:
        return self.layer_s[key] / self.steps * 1e3


def _load_bench(name: str):
    import harness

    return harness.bench_module(name)


# ---------------------------------------------------------------------------
# Name stacks from the trace's HLO
# ---------------------------------------------------------------------------

def _hlo_class():
    """The few fields of xla's HloProto that name stacks need
    (xla/service/hlo.proto), declared here: no compiled copy is installed."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    f = descriptor_pb2.FileDescriptorProto(name="hlo_names.proto", package="hnames",
                                           syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto

    def msg(name, fields):
        m = f.message_type.add(name=name)
        for fname, num, repeated, tname in fields:
            fd = m.field.add(name=fname, number=num,
                             label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL,
                             type=T.TYPE_MESSAGE if tname else T.TYPE_STRING)
            if tname:
                fd.type_name = tname

    msg("OpMetadata", [("op_name", 2, False, None)])
    msg("Instruction", [("name", 1, False, None),
                        ("metadata", 7, False, ".hnames.OpMetadata")])
    msg("Computation", [("instructions", 2, True, ".hnames.Instruction")])
    msg("Module", [("computations", 3, True, ".hnames.Computation")])
    msg("Hlo", [("hlo_module", 1, False, ".hnames.Module")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("hnames.Hlo"))


def hlo_op_names(raw: bytes) -> dict:
    """{instruction name: op_name} of the train step's HLO in the trace
    ``raw`` (an XSpace)."""
    space = _load_bench("tests/trim_trace")._schema()()
    space.ParseFromString(raw)
    hlo_cls, out = None, {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for md in plane.event_metadata.values():
            if not md.name.startswith(STEP_PROGRAM):
                continue
            for st in md.stats:
                if stat_names.get(st.metadata_id) == HLO_PROTO_STAT:
                    hlo_cls = hlo_cls or _hlo_class()
                    hlo = hlo_cls()
                    hlo.ParseFromString(st.bytes_value)
                    for comp in hlo.hlo_module.computations:
                        for ins in comp.instructions:
                            out[ins.name] = ins.metadata.op_name
    return out


def name_stack(ev, inst: str, names: dict) -> str:
    """The op_name of an ``XLA Ops`` event of HLO instruction ``inst``."""
    if inst in names:
        return names[inst]
    return next((str(v) for k, v in ev.stats if k == "tf_op"), "")


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def reduce(path, *, steps: int):
    """The trace at ``path`` by scope; None where no operation carries one."""
    from jax.profiler import ProfileData

    tr = _load_bench("trace")
    pd = ProfileData.from_file(str(path))
    spans = tr.host_spans(pd)
    planes = tr.device_planes(pd)
    if not spans or not planes:
        return None
    w0, w1 = spans[0][0], max(e for _, e, _ in spans)
    names = hlo_op_names(pathlib.Path(path).read_bytes())
    layer_s = dict.fromkeys((*SCOPES, UNSCOPED), 0.0)
    busy = exch = exposed = 0.0
    for plane in planes:
        sync, asyn = [], []
        for line in plane.lines:
            if line.name not in (tr.OPS_LINE, tr.ASYNC_LINE):
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    stack = name_stack(ev, tr.op_name(ev).lstrip("%"), names)
                    rec = (s, e, layer_of(stack))
                    (sync if line.name == tr.OPS_LINE else asyn).append(rec)
        for _, _, layer, t in tr.self_times(sync):
            layer_s[layer] += t
        busy += sum(e - s for s, e in tr.merge([(s, e) for s, e, _ in sync]))
        mine = tr.merge([(s, e) for s, e, x in sync + asyn if x == EXCHANGE])
        others = tr.merge([(s, e) for s, e, x in sync if x != EXCHANGE])
        exch += sum(e - s for s, e in mine)
        exposed += sum(tr.uncovered(iv, others) for iv in mine)
    if all(layer_s[k] == 0.0 for k in SCOPES):
        return None
    scale = 1e-9 / len(planes)
    return Scoped(steps=steps, devices=len(planes), window_s=(w1 - w0) * 1e-9,
                  busy_s=busy * scale,
                  layer_s={k: v * scale for k, v in layer_s.items()},
                  exchange_s=exch * scale, exchange_exposed_s=exposed * scale)


def newest_trace():
    """The trace the traced run just wrote (the rule of
    ``harness.traced_window``), or None."""
    import harness

    files = sorted(harness.TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


@functools.lru_cache(maxsize=4)
def _reduce_cached(path: str, mtime_ns: int, steps: int):
    return reduce(path, steps=steps)


def of(ctx):
    """The newest trace by scope, once per file for every reader; None where
    there is no trace or no scope in it."""
    path = newest_trace()
    if path is None:
        return None
    return _reduce_cached(str(path), path.stat().st_mtime_ns, ctx.reduced.steps)


def layer_ms(ctx, key: str):
    r = of(ctx)
    return None if r is None else r.ms(key)


class ScopedContext:
    """A reader's ``ctx`` whose layers are read by scope: an existing
    roofline reader, given it, counts its own bytes over the scoped time."""

    def __init__(self, ctx):
        self._ctx = ctx

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def layer_ms(self, key: str):
        ms = layer_ms(self._ctx, key)
        return ms if ms else None

    def roofline(self, key: str, bytes_per_step: float):
        return _load_bench("metrics_context").Context.roofline(
            self, key, bytes_per_step)


def main(path) -> None:
    r = reduce(path, steps=1)
    if r is None:
        print("no operation of the trace carries a scope")
        return
    print(f"{r.devices} device(s), window {r.window_s * 1e3:.3f} ms, "
          f"busy {r.busy_s * 1e3:.3f} ms")
    for k, v in r.layer_s.items():
        print(f"  {k:10s} {v * 1e3:10.3f} ms")
    print(f"  exchange {r.exchange_s * 1e3:.3f} ms, exposed "
          f"{r.exchange_exposed_s * 1e3:.3f} ms")


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    main(sys.argv[1])
