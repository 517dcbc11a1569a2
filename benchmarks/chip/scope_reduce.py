"""Device time of a traced window by program phase.

The program names its phases with `jax.named_scope` (README, "Reading a
profile of a job").  XLA keeps each op's name stack in its HLO
instruction's metadata, the `op_name`, e.g.
"jit(train_step)/vmap(transpose(jvp()))/while/body/closed_call/mlp/
jit(masked_matmul_dx)/pallas_call".  A fusion carries the op_name of its
root (of one output where the root is a tuple of several, of the op a
root convert or bitcast takes).  Device `XLA Ops` events name only the
instruction, so this reads each program's HLO proto from the trace's
`/host:metadata` plane (a walk of the protobuf wire format: no XPlane
bindings are installed), maps instruction names to op_names, puts each
op event in the module execution (`XLA Modules` event) that holds it,
and counts its time toward the innermost name of a given vocabulary on
its op_name path.  A transform's wrapping (`vmap(...)`,
`transpose(jvp(...))`) is taken off a path's part; a function's
(`jit(...)`) marks a function, not a scope.  An op_name that XLA merged
from several (`a;b`) counts as its first.

Every module execution the trace holds counts, whole: the benchmark
starts the trace at its window and stops it at the window's end, and the
device's clock in a trace can lead the host's by a millisecond, so the
host span `window` cannot cut executions at its edges.  It bounds the
device-to-host transfers counted, which are host events.  Ops that hold
other ops (`trace_reduce.NESTING`) count toward nothing.  So a module's
phases, `None` (no name of the vocabulary) included, add up to the
module's op time.

    python3 benchmarks/chip/scope_reduce.py <trace dir or .xplane.pb[.gz]>

prints the op time of every module and scope per execution.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import os
import re
import sys

MODULES_LINE = "XLA Modules"
D2H = "tpu::System::TransferFromDevice"
HLO_PROTO = b"Hlo Proto"
_WRAP = re.compile(r"^(\w+)\((.*)\)$")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


# -- protobuf wire format ---------------------------------------------------


def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of a serialized message: ints for varints,
    bytes for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"wire type {wire} in field {num}")
        yield num, v


def _first(b, num, default=None):
    return next((v for k, v in _fields(b) if k == num), default)


def _ints(values):
    """A repeated integer field's values, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


# -- HLO protos of the trace's programs -------------------------------------


@dataclasses.dataclass
class Program:
    module: str                  # HLO module name, e.g. "jit_train_step"
    op_name: dict                # instruction name -> op_name
    fusions: dict                # fusion -> (its root, its instructions)


def _program(hlo_proto: bytes) -> Program:
    """A Program from a serialized `HloProto` (field numbers of XLA's
    hlo.proto)."""
    mod = _first(hlo_proto, 1, b"")          # HloProto.hlo_module
    comps, calls, op_name = {}, {}, {}
    for num, comp in _fields(mod):
        if num != 3:                         # HloModuleProto.computations
            continue
        by_id = {}
        for k, ins in _fields(comp):
            if k != 2:
                continue
            name, called, ins_id = None, [], 0
            for f, v in _fields(ins):         # HloInstructionProto
                if f == 1:                   # name
                    name = v.decode()
                elif f == 2 and v == b"fusion":   # opcode
                    calls[name] = called
                elif f == 7:                 # metadata.op_name
                    op_name[name] = (_first(v, 2, b"") or b"").decode()
                elif f == 35:                # id
                    ins_id = v
                elif f == 38:                # called_computation_ids
                    called.append(v)
            op_name.setdefault(name, "")
            by_id[ins_id] = name
        # HloComputationProto id -> (its root, its instructions)
        comps[_first(comp, 5, 0)] = (by_id.get(_first(comp, 6, 0)),
                                     list(by_id.values()))
    fusions = {f: comps[_ints(c)[0]] for f, c in calls.items()
               if c and _ints(c)[0] in comps}
    return Program(module=(_first(mod, 1, b"") or b"").decode(),
                   op_name=op_name, fusions=fusions)


def programs(xspace: bytes) -> dict:
    """{program id: Program} from the `/host:metadata` plane."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        # XPlane.stat_metadata: the id of the stat named "Hlo Proto"
        hlo_stat = {_first(e[2], 1, 0)
                    for e in (dict(_fields(x)) for k, x in _fields(plane)
                              if k == 5)
                    if _first(e[2], 2) == HLO_PROTO}
        for k, x in _fields(plane):
            if k != 4:                       # XPlane.event_metadata
                continue
            meta = dict(_fields(x))[2]       # XEventMetadata: one program
            for f, stat in _fields(meta):    # its XStats: bytes_value (6)
                if f == 5 and _first(stat, 1, 0) in hlo_stat:
                    out[_first(meta, 1, 0)] = _program(_first(stat, 6))
    return out


def scope_of(op_name: str, vocab) -> str | None:
    """The innermost name of `vocab` on an op_name's path."""
    found = None
    for part in op_name.split(";", 1)[0].split("/"):
        while (m := _WRAP.match(part)) and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
        if part in vocab:
            found = part
    return found


# -- the reduction ----------------------------------------------------------


@dataclasses.dataclass
class Phases:
    window_s: float
    scope_s: dict          # (module, scope or None) -> device s
    module_s: dict         # module -> device s of its ops
    module_calls: dict     # module -> executions in the trace
    d2h: int               # device-to-host transfers begun in the window
    unknown_ops: int       # op events whose instruction no proto holds

    def per_call_ms(self, module: str, scope) -> float | None:
        calls = self.module_calls.get(module)
        if not calls:
            return None
        return 1e3 * self.scope_s.get((module, scope), 0.0) / calls

    def table(self, vocab) -> dict:
        """{module: {"calls", "op_ms", scope..., "unscoped"}}, ms per
        execution."""
        out = {}
        for mod, calls in sorted(self.module_calls.items()):
            row = {"calls": calls,
                   "op_ms": 1e3 * self.module_s.get(mod, 0.0) / calls}
            for s in vocab:
                if (mod, s) in self.scope_s:
                    row[s] = self.per_call_ms(mod, s)
            row["unscoped"] = self.per_call_ms(mod, None)
            out[mod] = row
        return out


def _read(path: str) -> bytes:
    from benchmarks.chip import trace_reduce as TR
    if os.path.isdir(path):
        path = TR.find_xplane(path)
    with open(path, "rb") as f:
        data = f.read()
    return gzip.decompress(data) if path.endswith(".gz") else data


def fresh_text(jitted, *args) -> str:
    """The text of `jitted` compiled anew for `args`.  JAX's persistent
    compilation cache keys a program without its op_name metadata, so a
    cached executable (and the HLO proto a trace holds of it) carries
    the op_names of whichever version of the program compiled first."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jitted.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def text_op_names(hlo_text: str) -> tuple:
    """(module, {instruction: op_name}) of a compiled program's text
    (`fresh_text`)."""
    module = re.search(r"^HloModule ([^\s,]+)", hlo_text, re.M).group(1)
    names = {}
    for name, rest in re.findall(r"^\s*(?:ROOT )?%(\S+) = (.*)$",
                                 hlo_text, re.M):
        op = re.search(r'op_name="([^"]*)"', rest)
        names[name] = op.group(1) if op else ""
    return module, names


def reduce(path: str, vocab, op_names=None) -> Phases:
    """Device time of the trace at `path` (a profiler directory or an
    `.xplane.pb`, gzipped if it ends in `.gz`) by module and by the
    innermost name of `vocab` on each op's path.  `op_names` ({module:
    {instruction: op_name}}, as `text_op_names` reads them) stand in for
    the trace's protos of those modules."""
    from jax.profiler import ProfileData
    from benchmarks.chip import trace_reduce as TR
    data = _read(path)
    progs = programs(data)
    op_names = op_names or {}
    pd = ProfileData.from_serialized_xspace(data)
    windows, d2h_starts, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == TR.WINDOW:
                        windows.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
                    elif ev.name == D2H:
                        d2h_starts.append(ev.start_ns)
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {l.name: list(l.events) for l in plane.lines}
            if TR.OPS_LINE in lines and MODULES_LINE in lines:
                devices.append(lines)
    if not windows:
        raise ValueError("the trace holds no `window` host span")
    if not devices:
        raise ValueError("the trace holds no device modules and ops")
    w0, w1 = windows[0]
    scope_s, module_s = collections.Counter(), collections.Counter()
    calls, unknown = collections.Counter(), 0
    for lines in devices:
        execs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in lines[MODULES_LINE])
        starts = [e[0] for e in execs]
        for _, _, name in execs:
            m = _MODULE.match(name)
            calls[m.group(1) if m else name] += 1
        for ev in lines[TR.OPS_LINE]:
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            if k < 0 or ev.start_ns >= execs[k][1]:
                continue
            instr = ev.name.split(" = ", 1)[0].lstrip("%").strip()
            if TR.base_name(instr) in TR.NESTING:
                continue
            m = _MODULE.match(execs[k][2])
            mod = m.group(1) if m else execs[k][2]
            prog = progs.get(int(m.group(2))) if m else None
            names = op_names.get(mod) or (prog.op_name if prog else {})
            op = names.get(instr)
            unknown += op is None
            s = ev.duration_ns * 1e-9
            scope_s[(mod, scope_of(op or "", vocab))] += s
            module_s[mod] += s
    return Phases(window_s=(w1 - w0) * 1e-9, scope_s=dict(scope_s),
                  module_s=dict(module_s), module_calls=dict(calls),
                  d2h=sum(w0 <= t < w1 for t in d2h_starts),
                  unknown_ops=unknown)


# the phases the program names (README, "Reading a profile of a job")
TRAIN_SCOPES = ("embed_head", "attention", "attention_core", "mlp",
                "regularizer", "optimizer")
ROUND_SCOPES = ("uplink", "codec_meter", "fold", "downlink")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-2].strip(), file=sys.stderr)
        return 2
    ph = reduce(argv[0], TRAIN_SCOPES + ROUND_SCOPES)
    print(json.dumps({"window_s": ph.window_s, "d2h": ph.d2h,
                      "unknown_ops": ph.unknown_ops,
                      "modules": ph.table(TRAIN_SCOPES + ROUND_SCOPES)}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    sys.exit(main())
