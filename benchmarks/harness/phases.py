"""Profiler trace -> device time per engine phase, and the program's own
host spans.

The program names each phase of its quantum program with
``jax.named_scope`` (``graphite_tpu/obs/scopes.py``); every compiled op
carries the scopes it was traced under as components of its ``op_name``
metadata.  An op's phase is the innermost ``engine.*`` component; an op
with none is ``unscoped`` (loop carries, progress reductions, the copies
XLA adds between loop iterations).  The program's host spans
(``graphite_tpu/obs/spans.py``, written as ``TraceAnnotation`` while its
tracer is enabled) sit on the host plane, on the device trace's clock.

The device ops and the window are ``xtrace.reduce``'s: the ops of each
device plane, clipped to the ``bench.window`` span.  Each op counts its
own time, the part no op nested in it covers, so the phases sum to the
device's busy time.  A v5e trace gives each op its name, offset and
duration only, so the op_names come from the HLO text of the executable
that ran (``op_names_from_hlo``): a traced run of the harness lowers and
compiles the window's program once more in set-up, which finds it in
jax's caches, and reads ``compile().as_text()``.

The harness does not set ``jax_compilation_cache_include_metadata_in_key``
(a traced run would pay a second cold compile).  JAX's persistent compile
cache keys a program without its debug info, so an executable cached from
a program that differs only in its scopes comes back with that program's
metadata.  A checkout keeps its own cache (``<checkout>/.jax_cache``),
with its own commit's executables, so this happens in one case only: two
programs that differ only in their scopes sharing one
``JAX_COMPILATION_CACHE_DIR``.  The split then follows the scopes of the
program that was cached first.

``load`` reads the trace once for both reductions: its ops carry the
op_name as a fourth field, which ``xtrace.reduce`` ignores, and its spans
are the harness's (``bench.*``) and the program's.

``PHASES`` is this module's own copy of the scope names: a scope the
program renames keeps its new name here, beside the old one at 0, so a
rename shows as a missing phase and never moves time into ``unscoped``.
"""

from __future__ import annotations

import re

import xtrace

PHASES = ("engine.barrier", "engine.schedule", "engine.sample",
          "engine.window_walk", "engine.complex_slot", "engine.fast_forward",
          "engine.resolve_memory", "engine.resolve_sync")
SCOPE_PREFIX = "engine."
UNSCOPED = "unscoped"
PROGRAM_SPANS = ("sim.compile+window", "sim.window", "sim.dispatch",
                 "sim.sync", "trace.device_upload", "trace.scan",
                 "state.alloc")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}")


def phase_of(op_name) -> str:
    """The innermost ``engine.*`` component of an op_name, or
    ``unscoped``."""
    for part in reversed((op_name or "").split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def op_names_from_hlo(text: str) -> dict:
    """Instruction name -> op_name, from a compiled module's HLO text
    (``jit(f).lower(...).compile().as_text()``).

    The TPU compiler makes ops that carry no metadata of their own: the
    two-word halves of an int64 scatter, the loop that relays out a
    large array between a scatter and a gather.  Such an op takes the
    op_name of the ops fused into it (the last one with metadata in its
    fused computation), else that of the op whose computation it lives
    in (the while, conditional or call that runs it), so XLA's own work
    lands in the phase whose code it serves.  The copies that carry a
    loop's state from one iteration to the next live in the loop's
    body, whose while op lies outside every phase: they stay unscoped.
    """
    own, fused, comp_of, comp_ops, callers = {}, {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
                comp_ops[comp] = []
            continue
        name = m.group(1)
        comp_of[name] = comp
        comp_ops.setdefault(comp, []).append(name)
        md = _OP_NAME.search(line)
        if md:
            own[name] = md.group(1)
        f = _FUSED.search(line)
        if f:
            fused[name] = f.group(1)
        for a, b in _CALLED.findall(line):
            for callee in ([a] if a else b.replace("%", "").split(",")):
                callers.setdefault(callee.strip(), []).append(name)

    inner = {}

    def from_fused(c):
        if c not in inner:
            inner[c] = None
            for op in reversed(comp_ops.get(c, ())):
                got = own.get(op) or (from_fused(fused[op])
                                      if op in fused else None)
                if got:
                    inner[c] = got
                    break
        return inner[c]

    out = {}

    def resolve(name):
        if name not in out:
            out[name] = None          # a cycle resolves to nothing
            got = own.get(name)
            if not got and name in fused:
                got = from_fused(fused[name])
            if not got:
                for caller in callers.get(comp_of.get(name), ()):
                    got = resolve(caller)
                    if got:
                        break
            out[name] = got
        return out[name]

    for name in comp_of:
        resolve(name)
    return {k: v for k, v in out.items() if v}


def load(path: str, op_names: dict, device_plane=xtrace.DEVICE_PLANE,
         op_line: str = xtrace.OP_LINE):
    """(device ops per device plane, spans): ops as (name, start_ns,
    end_ns, op_name), the op_name from ``op_names``
    (``op_names_from_hlo``); spans as (name, start_ns, end_ns) for the
    harness's ``bench.*`` spans and the program's spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    wanted = set(PROGRAM_SPANS)
    match = device_plane.match if hasattr(device_plane, "match") \
        else (lambda n: n.startswith(device_plane))
    devices, spans = {}, []
    for plane in pd.planes:
        lines = list(plane.lines)
        if match(plane.name):
            chosen = [ln for ln in lines if ln.name.startswith(op_line)] \
                or lines
            ops = []
            for ln in chosen:
                for ev in ln.events:
                    name = xtrace.short_name(ev.name)
                    ops.append((name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                op_names.get(name)))
            devices[plane.name] = ops
        for ln in lines:
            for ev in ln.events:
                if ev.name in wanted \
                        or ev.name.startswith(xtrace.SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return devices, spans


def self_time(ops):
    """(op, start, end) pieces of each op's interval that no op nested in
    it covers.  A while or conditional spans its body's ops on the same
    line; the stretches between them (the loop's condition, the branch
    choice) are its own.  The pieces are disjoint and cover the union of
    the ops, so they sum to ``xtrace.busy_ns``."""
    out, stack = [], []               # stack: [op, end of covered part]

    def close(entry):
        op, covered = entry
        if op[2] > covered:
            out.append((op, covered, op[2]))
        if stack:
            stack[-1][1] = max(stack[-1][1], min(op[2], stack[-1][0][2]))

    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0][2] <= op[1]:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            if op[1] > parent[1]:
                out.append((parent[0], parent[1], op[1]))
            parent[1] = max(parent[1], op[1])
        stack.append([op, op[1]])
    while stack:
        close(stack.pop())
    return out


def reduce(devices, spans) -> dict:
    """Device seconds per phase (every name of ``PHASES`` present, 0.0
    when none of its ops ran), averaged over the device planes as
    ``xtrace.reduce`` averages busy time; the phase of each op name seen;
    and the durations, in seconds, of each program span that ends inside
    the window.  Each op's own time (``self_time``) inside the window
    counts under its phase, so the phases sum to the window's busy time.
    Empty when the trace has no window or no device."""
    win = [sp for sp in spans if sp[0] == xtrace.WINDOW_SPAN]
    if not win or not devices:
        return {}
    t0, t1 = win[0][1], win[0][2]
    phase_s = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
    op_phase = {}
    for ops in devices.values():
        for (name, _s, _e, op_name), s, e in self_time(ops):
            d = min(e, t1) - max(s, t0)
            if d > 0:
                if name not in op_phase:
                    op_phase[name] = phase_of(op_name)
                ph = op_phase[name]
                phase_s[ph] = phase_s.get(ph, 0.0) + d / 1e9
    n = len(devices)
    span_s = {}
    for name, s, e in spans:
        if name in PROGRAM_SPANS and t0 <= e <= t1:
            span_s.setdefault(name, []).append((e - s) / 1e9)
    return {"phases": {k: v / n for k, v in phase_s.items()},
            "op_phase": op_phase, "spans": span_s}
