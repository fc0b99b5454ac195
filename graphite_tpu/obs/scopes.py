"""Device-side phase names of the quantum program.

Each engine phase runs under ``jax.named_scope(<name>)``, applied at the
function that does the phase's work.  A scope is metadata only: every op
traced inside it carries the name as a component of its ``op_name``
(HLO metadata), so a profiler trace of the compiled program splits its
device time by phase.  It adds no op and no state.

The progress reductions and loop carries of the round loops stay
unscoped on purpose: their cost is what is left when the phases are
summed.
"""

from __future__ import annotations

BARRIER = "engine.barrier"                # quantum.next_boundary + ctr_quantum
SCHEDULE = "engine.schedule"              # quantum.schedule_rotate
SAMPLE = "engine.sample"                  # quantum._maybe_sample
WINDOW_WALK = "engine.window_walk"        # core._block_retire (kernels/window)
COMPLEX_SLOT = "engine.complex_slot"      # core._complex_slot(_guarded)
FAST_FORWARD = "engine.fast_forward"      # core._fast_forward_guarded
RESOLVE_MEMORY = "engine.resolve_memory"  # resolve.resolve_memory
RESOLVE_SYNC = "engine.resolve_sync"      # resolve.resolve's sync_pass

PHASES = (BARRIER, SCHEDULE, SAMPLE, WINDOW_WALK, COMPLEX_SLOT,
          FAST_FORWARD, RESOLVE_MEMORY, RESOLVE_SYNC)

# Sub-phase: the contended mesh's flight loop (noc_flight.flight), nested
# in RESOLVE_MEMORY (memory network) or COMPLEX_SLOT (user network).  Being
# the innermost ``engine.*`` scope of its ops, it takes their time out of
# the enclosing phase.  Only a contended network traces it.
NOC_FLIGHT = "engine.noc_flight"
