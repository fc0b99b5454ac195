#!/usr/bin/env python3
"""Typed-cost annotation of captured traces via static decode.

The capture runtime records one COMPUTE event per executed basic block
with pc = the block's ``__sanitizer_cov_trace_pc`` return address and an
ESTIMATED instruction count (native/src/tsan_capture.cc cov_block).  The
reference instead decodes every static instruction once into a typed
cost (pin/instruction_modeling.cc:157-348).  This tool closes that gap
after the fact:

  1. ``objdump -d`` the captured binary once,
  2. every ``call <__sanitizer_cov_trace_pc>`` site starts a block; the
     block body runs from the call's return address to the next call
     site (GCC plants exactly one probe at each basic-block entry, so
     consecutive probe sites delimit block bodies),
  3. count the body's instructions and classify them by mnemonic into
     the engine's InstructionType classes, pricing the block with the
     same [core/static_instruction_costs] table the engine uses,
  4. rewrite each COMPUTE event's (cost, icount) from its pc's block.
     Instrumentation calls (__tsan_*, probe calls) are excluded from
     the counts — they are capture overhead, not target work.

Usage: python tools/annotate_trace.py [--verbose] BINARY TRACE_IN [TRACE_OUT]
(defaults to rewriting TRACE_IN in place).  Also importable:
``annotate_raw(binary, trace_in) -> (hits, total)``.

Progress chatter ("N/M compute events typed") is silenced unless
--verbose (or verbose=True): bench.py annotates one capture per row and
the per-trace lines dominated the visible tail of a timed-out bench
(round 5).  Anomalies — no static blocks decoded, annotation
refused under branch thinning — always print.
"""

from __future__ import annotations

import re
import subprocess
import sys

import numpy as np

# Mnemonic must start with a letter: objdump continuation lines of
# >7-byte instructions are bytes-only ("  7:\t33 22 11 ") and must not
# read as a phantom instruction.
_INSN = re.compile(
    r"^\s*([0-9a-f]+):\s+(?:[0-9a-f]{2} )+\s*([a-z][a-z0-9.]*)\s*(.*)$")
_COV_CALL = re.compile(
    r"^\s*([0-9a-f]+):\s+(?:[0-9a-f]{2} )+\s*call[ql]?\s+\S+ "
    r"<__sanitizer_cov_trace_pc(?:@plt)?>")
_FUNC_HDR = re.compile(r"^[0-9a-f]+ <[^>]+>:$")

# Mnemonic -> InstructionType.config_key (x86-64; anything unlisted is
# 'generic').  Mirrors the groups of the reference decode
# (pin/instruction_modeling.cc:157-348).
def _classify(mnemonic: str) -> str:
    m = mnemonic
    if m.startswith(("mov", "lea", "push", "pop", "cmov")):
        return "mov"
    if m.startswith(("add", "sub", "inc", "dec", "and", "or", "xor",
                     "not", "neg", "shl", "shr", "sar", "sal", "cmp",
                     "test", "rol", "ror", "adc", "sbb")) \
            and not m.endswith(("ss", "sd", "ps", "pd")):
        return "ialu"
    if m.startswith(("imul", "mul")) and not m.endswith(
            ("ss", "sd", "ps", "pd")):
        return "imul"
    if m.startswith(("idiv", "div")) and not m.endswith(
            ("ss", "sd", "ps", "pd")):
        return "idiv"
    if m.startswith(("f",)) and m not in ("fence",):
        # x87: fadd/fsub -> falu, fmul -> fmul, fdiv -> fdiv
        if m.startswith("fmul"):
            return "fmul"
        if m.startswith("fdiv"):
            return "fdiv"
        return "falu"
    if m.endswith("ss"):
        if m.startswith(("div", "sqrt")):
            return "fdiv"
        return "xmm_ss"
    if m.endswith("sd") and not m.startswith("cltd"):
        if m.startswith(("div", "sqrt")):
            return "fdiv"
        return "xmm_sd"
    if m.endswith(("ps", "pd")):
        return "xmm_ps"
    if m.startswith(("j", "call", "ret", "loop")):
        return "branch"
    return "generic"


_SKIP_CALL = re.compile(r"<(__tsan_|__sanitizer_|_Carbon|Carbon)")

_DEFAULT_COSTS = {
    "generic": 1, "mov": 1, "ialu": 1, "imul": 3, "idiv": 18,
    "falu": 3, "fmul": 5, "fdiv": 6, "xmm_ss": 6, "xmm_sd": 6,
    "xmm_ps": 6, "branch": 1,
}


def block_table(binary: str, costs=None):
    """{ret_addr: (icount, cost_cycles)} for every probe-delimited block."""
    costs = dict(_DEFAULT_COSTS, **(costs or {}))
    out = subprocess.run(["objdump", "-d", binary], check=True,
                         capture_output=True, text=True).stdout
    # Pass 1: probe call sites (block starts) + function boundaries, in
    # address order.  A block body must not run past its function's end
    # — without the boundary, the last block of every instrumented
    # function would swallow the next function's pre-probe prologue
    # (and the address-wise last app block the whole uninstrumented
    # runtime).
    sites = []          # probe call addresses
    insns = []          # (addr, mnemonic, operands, func_id)
    func_id = 0
    for line in out.splitlines():
        if _FUNC_HDR.match(line):
            func_id += 1
            continue
        mc = _COV_CALL.match(line)
        mi = _INSN.match(line)
        if mi:
            addr = int(mi.group(1), 16)
            insns.append((addr, mi.group(2), mi.group(3), func_id))
            if mc:
                sites.append(addr)
    if not sites:
        return {}
    # ret addr of call k = address of the next instruction after it.
    addr_index = {a: i for i, (a, _, _, _) in enumerate(insns)}
    table = {}
    site_set = set(sites)
    for call_addr in sites:
        i = addr_index[call_addr] + 1
        if i >= len(insns):
            continue
        ret_addr = insns[i][0]
        fid = insns[i][3]
        icount = 0
        cost = 0
        while i < len(insns):
            addr, mn, ops, f = insns[i]
            if addr in site_set or f != fid:   # next probe / next func
                break
            # Exclude instrumentation calls (capture overhead).
            if mn.startswith("call") and _SKIP_CALL.search(ops):
                i += 1
                continue
            icount += 1
            cost += costs.get(_classify(mn), 1)
            i += 1
        if icount > 0:
            table[ret_addr] = (icount, cost)
    return table


def annotate_raw(binary: str, trace_in: str, trace_out=None, costs=None,
                 verbose: bool = False):
    """Rewrite COMPUTE (cost, icount) in a RAW capture file from the
    binary's block table — BEFORE binio's address compaction remaps the
    recorded pcs (load_binary_trace keeps only page-offset bits of code
    addresses).  COMPUTE events whose pc is unknown (library code) keep
    their runtime estimates.  The capture link uses -no-pie
    (tools/capture_build.sh) so runtime pcs equal objdump addresses."""
    import struct

    from graphite_tpu.events.binio import MAGIC, _REC
    from graphite_tpu.isa import EventOp

    import os
    if os.environ.get("CARBON_TSAN_BRANCH_EVERY", "1") not in ("", "1"):
        # With branch thinning, one COMPUTE event aggregates several
        # basic blocks' instructions at the LAST block's pc — rewriting
        # it to one block's static count would drop work.  Refuse.
        print("annotate_trace: CARBON_TSAN_BRANCH_EVERY != 1 — COMPUTE "
              "events aggregate blocks; skipping annotation",
              file=sys.stderr)
        return 0, 0
    table = block_table(binary, costs)
    trace_out = trace_out or trace_in
    with open(trace_in, "rb") as f:
        if f.read(8) != MAGIC:
            raise ValueError(f"{trace_in}: not a capture trace")
        (num_tiles,) = struct.unpack("<I", f.read(4))
        per_tile = []
        for _ in range(num_tiles):
            (n,) = struct.unpack("<I", f.read(4))
            per_tile.append(np.frombuffer(
                f.read(n * _REC.itemsize), dtype=_REC).copy())
    # Vectorized rewrite: sorted block-table lookup per COMPUTE pc
    # (captures emit one COMPUTE per executed block — 10^7+ events for a
    # real benchmark; a per-event Python loop would cost minutes).  An
    # empty table (binary built without coverage, foreign guard ABI)
    # matches nothing and the trace passes through unmodified —
    # trace_out is still written either way.
    if not table:
        print("annotate_trace: no static blocks decoded; keeping runtime "
              "estimates", file=sys.stderr)
    keys = np.array(sorted(table.keys()), dtype=np.int64)
    vals = (np.array([table[k] for k in keys], dtype=np.int64)
            if table else np.zeros((0, 2), dtype=np.int64))
    total = hits = 0
    for rec in per_tile:
        comp = rec["op"] == int(EventOp.COMPUTE)
        pcs = rec["addr"][comp].astype(np.int64)
        total += len(pcs)
        if len(keys):
            idx = np.searchsorted(keys, pcs)
            ok = idx < len(keys)
            idx = np.minimum(idx, len(keys) - 1)
            ok &= keys[idx] == pcs
            hits += int(ok.sum())
            ic = rec["arg2"][comp].copy()
            cost = rec["arg"][comp].copy()
            ic[ok] = vals[idx[ok], 0]
            cost[ok] = vals[idx[ok], 1]
            rec["arg2"][comp] = ic
            rec["arg"][comp] = cost
    with open(trace_out, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<I", num_tiles))
        for rec in per_tile:
            f.write(struct.pack("<I", len(rec)))
            f.write(rec.tobytes())
    if verbose:
        print(f"annotate_trace: {hits}/{total} compute events typed "
              f"({len(table)} static blocks)", file=sys.stderr)
    return hits, total


def main(argv):
    args = [a for a in argv[1:] if a not in ("--verbose", "-v")]
    verbose = len(args) != len(argv) - 1
    if len(args) < 2:
        print(__doc__)
        return 2
    binary, tin = args[0], args[1]
    tout = args[2] if len(args) > 2 else tin
    import os
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    annotate_raw(binary, tin, tout, verbose=verbose)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
