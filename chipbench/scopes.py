"""Device time by scope: from a profiler trace to the program's own
names, and the idle gaps by the program's own spans.

The program names the parts of its device programs with
``jax.named_scope`` (``rocalphago_tpu/obs/scopes.py``: ``train.*``,
``ply.*``, ``encode.*``, ``eval.*``, ``mcts.*``; the networks carry
Flax's module scopes) and puts its host spans on the profiler's clock
as ``rocalphago.<name>`` (``obs/trace.py``). This module reduces a
trace by those names.

**Where the scope path lives on a TPU's xplane** (looked at by hand,
one trace of the train step on a v5e, jax 0.9.0 / libtpu 0.0.34):

* NOT in the events. An ``XLA Ops`` event's name is the HLO line with
  its metadata cut off (``%multiply_add_fusion.4 = f32[3,3,256,256]{…}
  fusion(…), kind=kOutput, calls=%fused_computation.141``) and its
  stats are ``device_offset_ps``, ``device_duration_ps`` and ``Time
  Scale Multiplier`` — no ``tf_op``, no ``long_name``. The device
  plane's lines are ``Steps``, ``XLA Modules``, ``XLA Ops``, ``Async
  XLA Ops`` and ``TC Overlay``: no ``Framework Name Scope`` line.
* In the plane ``/host:metadata``. It has no line; its event metadata
  holds one entry per program that ran (``jit_train_step(<id>)``, the
  name an ``XLA Modules`` event carries), each with one bytes stat
  ``Hlo Proto``: the compiled module, where every instruction has
  ``metadata.op_name`` —
  ``jit(train_step)/transpose(jvp(PolicyNet))/trunk/conv5/conv_general_dilated``.
  ``jax.profiler.ProfileData`` does not expose a plane's event
  metadata, so :func:`read_programs` reads those few fields off the
  protobuf wire itself (field numbers of ``xplane.proto`` and
  ``hlo.proto``, which protobuf never renumbers). The CPU backend
  writes the same plane, so a rehearsal runs the same code.

So an operation is found by (program, instruction name): the program
is the ``XLA Modules`` event that contains it in time (on the CPU,
the event's ``hlo_module``/``program_id`` stats), the instruction
name is the event's name up to `` = ``.

**The rule for fusions** (:func:`resolve`): a fusion is one event but
many instructions with different scopes. It is put down to the scope
of the convolution or dot inside it (searched through nested fused
computations) when it has one, else to its root's, else — a root
the compiler made, such as the bit-packed ReLU mask the forward pass
saves — to the scope most of its instructions carry, else to its
own metadata. The eleven ``multiply_add_fusion f32[3,3,F,F]`` of the
train step are a weight gradient (convolution, under
``transpose(jvp(PolicyNet))/trunk/convN``) whose root is the SGD
update (``train.update/add``): they are the backward pass's.

:func:`reduce` is arithmetic on plain lists, checked in
``tests/test_scopes.py`` on ``tests/data/scope_events.json``, a
sample cut from one TPU trace. :func:`account` takes the trace (a
window of its own: ``run.py`` deletes its trace before the layer
readers run) and is what the readers in ``layers/`` call.
"""

from __future__ import annotations

import json
import os
import shutil

from chipbench import trace_reduce

#: seconds of steady state the scoped window holds (whole blocks)
WINDOW_SECONDS = 2.0
#: prefixes of host spans kept: the program's and the benchmark's
SPAN_PREFIXES = ("rocalphago.", trace_reduce.SPAN_PREFIX)
#: opcodes whose scope a fusion takes when it contains one
HERO_OPCODES = ("convolution", "dot")
#: the train step's scopes (``rocalphago_tpu/obs/scopes.py``)
TRAIN_CLASSES = {"augment": "train.augment", "loss": "train.loss",
                 "update": "train.update"}
#: the earlier line holds this many scopes and spans, longest first
#: (a train step has 36; ``scopes.json`` has all of any program)
SAID = 60
#: the key under which time with no scope is filed
UNSCOPED = "(unscoped)"
HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------ the protobuf wire

def _varint(buf: bytes, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes):
    """``(field number, value)`` of one message: ints for varints,
    bytes for length-delimited and fixed fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, value


def _all(buf: bytes, number: int) -> list:
    return [v for f, v in _fields(buf) if f == number]


def _one(buf: bytes, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def _ids(buf: bytes, number: int) -> list:
    """A repeated int64 field, packed or not."""
    out = []
    for v in _all(buf, number):
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def _program(module: bytes) -> dict:
    """One ``HloModuleProto`` as ``{instruction: [opcode, op_name,
    [called computation, …]]}`` and ``{computation: [root
    instruction, [instruction, …]]}``."""
    comps = _all(module, 3)                     # computations
    comp_name = {_one(c, 5): _one(c, 1, b"").decode() for c in comps}
    instructions, computations = {}, {}
    for c in comps:
        names, by_id = [], {}
        for ins in _all(c, 2):                  # instructions
            name = _one(ins, 1, b"").decode()
            meta = _one(ins, 7)                 # OpMetadata
            op_name = (_one(meta, 2, b"").decode() if meta else "")
            called = [comp_name.get(i, "") for i in _ids(ins, 38)]
            instructions[name] = [_one(ins, 2, b"").decode(), op_name,
                                  called]
            names.append(name)
            by_id[_one(ins, 35)] = name
        computations[comp_name[_one(c, 5)]] = [
            by_id.get(_one(c, 6), ""), names]
    return {"instructions": instructions, "computations": computations}


def read_programs(xplane_path: str) -> dict:
    """``{program name: program}`` of every program whose HLO the
    trace carries (plane ``/host:metadata``); ``{}`` when it has no
    such plane."""
    with open(xplane_path, "rb") as f:
        space = f.read()
    programs = {}
    for plane in _all(space, 1):                # XSpace.planes
        if _one(plane, 2, b"") != b"/host:metadata":
            continue
        for entry in _all(plane, 4):            # event_metadata map
            meta = _one(entry, 2)               # XEventMetadata
            if meta is None:
                continue
            name = _one(meta, 2, b"").decode()
            for stat in _all(meta, 5):          # XStat
                proto = _one(stat, 6)           # bytes_value: HloProto
                module = _one(proto, 1) if proto else None
                if module:
                    programs[name] = _program(module)
    return programs


# ------------------------------------------------ scopes of a program

def resolve(program: dict) -> dict:
    """``{instruction: op_name}`` with the rule for fusions (module
    docstring) applied; an instruction with no metadata gets ``""``."""
    instructions = program["instructions"]
    computations = program["computations"]

    def inside(comp: str, depth: int = 0):
        """Every instruction of a fused computation, nested ones
        included."""
        for name in computations.get(comp, ("", []))[1]:
            opcode, op_name, called = instructions[name]
            yield opcode, op_name
            if opcode == "fusion" and depth < 8:
                for c in called:
                    yield from inside(c, depth + 1)

    def fused(called: list) -> str:
        named = [(opcode, op_name) for c in called
                 for opcode, op_name in inside(c) if op_name]
        for opcode, op_name in named:
            if opcode in HERO_OPCODES:
                return op_name
        for c in called:
            root = computations.get(c, ("", []))[0]
            op_name = instructions.get(root, ["", "", []])[1]
            if op_name:
                return op_name
        # a root the compiler made (a packed mask, a layout change):
        # the scope most of the fused instructions carry
        counts: dict = {}
        for _, op_name in named:
            counts.setdefault(scope_of(op_name), []).append(op_name)
        return max(counts.values(), key=len)[0] if counts else ""

    return {name: (fused(called) or op_name) if opcode == "fusion"
            else op_name
            for name, (opcode, op_name, called) in instructions.items()}


def scope_of(op_name: str) -> str:
    """The scope path of an ``op_name``: without the outermost
    ``jit(…)`` and without the primitive at its end —
    ``jit(train_step)/jvp(PolicyNet)/trunk/conv3/conv_general_dilated``
    is under ``jvp(PolicyNet)/trunk/conv3``; ``jit(train_step)/mul``
    is under no scope."""
    parts = op_name.split("/")
    if parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    return "/".join(parts[:-1]) or UNSCOPED


def train_class(scope: str) -> str:
    """Which part of a train step a scope path belongs to."""
    for cls, name in TRAIN_CLASSES.items():
        if name in scope:
            return cls
    if "transpose(jvp(" in scope:
        return "bwd"
    if "jvp(" in scope:
        return "fwd"
    return "unscoped"


# ------------------------------------------------ from the trace

def _instruction(event_name: str) -> str:
    """``%fusion.7 = s32[…] fusion(…)`` → ``fusion.7`` (a TPU event
    is the HLO line; a CPU event is the instruction's name)."""
    return event_name.partition(" = ")[0].lstrip("%")


def load(xplane_path: str) -> dict:
    """``{"device": {plane: {"ops": [[program, instruction,
    start_ns, dur_ns], …], "modules": [[program, start_ns, dur_ns],
    …]}}, "spans": [[name, start_ns, dur_ns], …], "programs": {name:
    program}}`` from one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device, spans, host_ops = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if trace_reduce.OP_LINES[0] not in lines:
                continue
            modules = sorted(
                (float(e.start_ns), float(e.start_ns + e.duration_ns),
                 e.name) for e in lines["XLA Modules"].events
            ) if "XLA Modules" in lines else []
            ops, m = [], 0
            for e in sorted(lines[trace_reduce.OP_LINES[0]].events,
                            key=lambda e: e.start_ns):
                start = float(e.start_ns)
                while m < len(modules) and modules[m][1] <= start:
                    m += 1
                program = (modules[m][2] if m < len(modules)
                           and modules[m][0] <= start else "")
                ops.append([program, _instruction(e.name), start,
                            float(e.duration_ns)])
            device[plane.name] = {
                "ops": ops,
                "modules": [[n, a, b - a] for a, b, n in modules]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                on_xla = line.name.startswith(
                    trace_reduce.HOST_XLA_LINES)
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
                    elif on_xla and e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            host_ops.append([
                                f"{stats.get('hlo_module')}"
                                f"({stats.get('program_id')})",
                                str(stats["hlo_op"]),
                                float(e.start_ns),
                                float(e.duration_ns)])
    if not device and host_ops:
        # a CPU rehearsal: XLA's own threads stand in for the device
        # (as in trace_reduce.load_events); never a chip number
        device[trace_reduce.HOST_STAND_IN] = {"ops": host_ops,
                                              "modules": []}
    return {"device": device, "spans": spans,
            "programs": read_programs(xplane_path)}


def _resolved(programs: dict, name: str) -> dict:
    """``{instruction: op_name}`` of the program an ``XLA Modules``
    event names. On the TPU the names match to the id. The CPU
    backend numbers a program it loads from the compile cache anew,
    so there the programs of the same name (one function at several
    shapes) are read together, the first that has an instruction
    giving its scope: near enough for a rehearsal, never used for a
    chip number."""
    if name in programs:
        return resolve(programs[name])
    stem, merged = name.partition("(")[0], {}
    for other in sorted(programs):
        if other.partition("(")[0] == stem:
            for instruction, op_name in resolve(
                    programs[other]).items():
                merged.setdefault(instruction, op_name)
    return merged


def reduce(events: dict) -> dict:
    """Device SELF time by scope over the ``chipbench.window`` span
    (else over everything traced): busy seconds, the window, seconds
    per scope path, the scope names the programs that ran define, how
    often each program ran (``XLA Modules`` events; none on a CPU),
    and all idle time by the innermost
    ``rocalphago.``/``chipbench.`` span over each gap."""
    spans = events["spans"]
    window = [s for s in spans if s[0] == trace_reduce.WINDOW_SPAN]
    planes = [p for _, p in sorted(events["device"].items())
              if p["ops"]]
    if not planes:
        raise ValueError("the trace has no device operation")
    if window:
        lo = min(s[1] for s in window)
        hi = max(s[1] + s[2] for s in window)
    else:
        lo = min(e[2] for p in planes for e in p["ops"])
        hi = max(e[2] + e[3] for p in planes for e in p["ops"])
    resolved = {}

    def scope(program: str, instruction: str) -> str:
        if program not in resolved:
            resolved[program] = _resolved(events["programs"], program)
        return scope_of(resolved[program].get(instruction, ""))

    index = trace_reduce._Spans(spans)
    busy_ns, by_scope, runs, idle = 0.0, {}, {}, {}
    for plane in planes:
        evs = trace_reduce._clip(
            [[f"{p}\t{i}", s, d] for p, i, s, d in plane["ops"]],
            lo, hi)
        merged = trace_reduce._union([[s, s + d] for _, s, d in evs])
        busy_ns += sum(e - s for s, e in merged)
        for key, t in trace_reduce.self_times(evs).items():
            program, _, instruction = key.partition("\t")
            sc = scope(program, instruction)
            by_scope[sc] = by_scope.get(sc, 0.0) + t
        for program, _, _ in trace_reduce._clip(plane["modules"],
                                                lo, hi):
            runs[program] = runs.get(program, 0) + 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                label = index.label(a, b)
                idle[label] = idle.get(label, 0.0) + (b - a)
    n = len(planes)
    names = sorted({scope_of(op) for r in resolved.values()
                    for op in r.values()})
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "by_scope": {k: v / n / 1e9 for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "scope_names": names,
        "program_runs": runs,
        "idle_by_span": [[k, v / n / 1e9] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])],
    }


# ------------------------------------------------ for the readers

def account(ctx) -> dict:
    """The by-scope account of this run: one more window of
    :data:`WINDOW_SECONDS` under a trace of its own, reduced, said on
    an earlier line, written to ``out/<cell>/scopes.json`` and kept
    on ``ctx`` for the next reader."""
    cached = getattr(ctx, "scope_account", None)
    if cached is not None:
        return cached
    import jax

    out_dir = os.path.join(HERE, "out", ctx.cell["name"])
    trace_dir = os.path.join(out_dir, "scope_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0         # the host stays quick
    traced = ctx.driver.window(
        WINDOW_SECONDS,
        on_start=lambda: jax.profiler.start_trace(
            trace_dir, profiler_options=options))
    jax.profiler.stop_trace()
    acct = reduce(load(trace_reduce.find_xplane(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    acct["window"] = {k: v for k, v in traced.items()
                      if isinstance(v, (int, float))}
    with open(os.path.join(out_dir, "scopes.json"), "w") as f:
        json.dump(acct, f, indent=1)
    print(json.dumps({"scope_account": dict(
        acct, scope_names=len(acct["scope_names"]),
        by_scope=dict(list(acct["by_scope"].items())[:SAID]),
        idle_by_span=acct["idle_by_span"][:SAID])}), flush=True)
    ctx.scope_account = acct
    return acct


def has_scope(acct: dict, name: str) -> bool:
    """Whether any program that ran defines a scope called ``name``
    (a program without it — the parent of the PR that brought the
    scopes — gives its reader nothing to read)."""
    return any(name in s for s in acct["scope_names"])


def seconds_under(acct: dict, *names: str) -> float:
    """Device self seconds under any scope path that holds one of
    ``names``."""
    return sum(t for s, t in acct["by_scope"].items()
               if any(n in s for n in names))


def share_pct(ctx, *names: str):
    """Percent of the device's busy time under the scopes ``names``;
    None when no program that ran defines any of them."""
    acct = account(ctx)
    if not acct["busy_s"] or not any(has_scope(acct, n) for n in names):
        return None
    return 100.0 * seconds_under(acct, *names) / acct["busy_s"]


def train_split(ctx):
    """Seconds per train step by class (``fwd``, ``bwd``, ``augment``,
    ``loss``, ``update``, ``unscoped``) and ``busy``; None when the
    window counted no step."""
    acct = account(ctx)
    steps = acct["window"].get("steps")
    if not steps:
        return None
    split = dict.fromkeys(
        ("fwd", "bwd", "augment", "loss", "update", "unscoped"), 0.0)
    for scope, t in acct["by_scope"].items():
        split[train_class(scope)] += t / steps
    split["busy"] = acct["busy_s"] / steps
    return split


def train_ms(ctx, cls: str):
    """Milliseconds per step of one class of :func:`train_split`;
    for a ``train.*`` class, None when the step has no such scope."""
    name = TRAIN_CLASSES.get(cls)
    if name is not None and not has_scope(account(ctx), name):
        return None
    split = train_split(ctx)
    return None if split is None else 1e3 * split[cls]
